"""repro.dist — the multi-device decomposition of the paper's recovery stack.

Module map (paper references are to "GPU-Accelerated Algorithms for
Compressed Signals Recovery with Application to Astronomical Imagery
Deblurring", arXiv:1707.02244):

    compat     shard_map / mesh constructors, used by every entry point
               below and by the subprocess test programs.
    sharding   logical->physical named-axis sharding rules for the model
               stack (DEFAULT_RULES, rules_for_arch, activate_rules,
               constrain, grad_reduce_boundary).  This is the GSPMD side:
               transformer training shards by annotation.
    fft        the four-step n = n1 x n2 decomposed FFT (paper Sec. 4's
               C = F^H diag(spec) F identity, made multi-device): layout_2d /
               unlayout_2d / freq_flat define the sharded layout; a circulant
               matvec costs exactly two transpose-collectives
               (make_distributed_fft, make_distributed_matvec).  ``overlap=K``
               splits each transpose into K chunked all-to-alls overlapped
               with the first local FFT stage (same payload modulo chunk
               zero-padding, same result).
    recovery   the *planned step functions* of CPADMM, paper Alg. 3, over
               that layout: the spectral inverse B = (rho C^T C + sigma
               I)^{-1} stays sharded in the frequency domain;
               dist_cpadmm_step is the paper-faithful 6-transform iteration,
               dist_cpadmm_step_fused batches it down to two all-to-alls per
               iteration; ``tail='pallas'`` runs the elementwise tail as the
               fused kernels/cpadmm_tail VMEM pass.  There is no driver
               here: ``repro.ops.plan(op, mesh)`` lowers an operator onto
               these steps (and onto planned CPISTA/FISTA matvecs), and the
               ``repro.core.solvers`` drivers run it — make_dist_cpadmm
               survives only as a deprecation shim over that API (removed
               in repro 0.2.0; deliberately not re-exported here).

The solvers here must agree with the single-device ``repro.core`` paths —
tests/test_dist_equiv.py and tests/test_plan.py pin the distributed-vs-core
match for every method, and tests/dist_progs/*.py exercise every module on
8 fake devices.
"""

_LAZY_MODULES = ("compat", "fft", "recovery", "sharding")

# Package-level symbol re-exports (PEP 562 lazy, like repro.ops).
# ``make_dist_cpadmm`` is deliberately NOT here and NOT in ``__all__``: the
# shim is deprecated (removal in repro 0.2.0) and stays reachable only by
# its full path ``repro.dist.recovery.make_dist_cpadmm`` until then.
_LAZY_SYMBOLS = {
    "make_mesh": "compat",
    "shard_map": "compat",
    "MODEL_AXIS": "fft",
    "layout_2d": "fft",
    "unlayout_2d": "fft",
    "freq_flat": "fft",
    "make_distributed_fft": "fft",
    "make_distributed_rfft": "fft",
    "make_distributed_matvec": "fft",
    "DistCpadmmParams": "recovery",
    "DistCpadmmState": "recovery",
    "dist_cpadmm_step": "recovery",
    "dist_cpadmm_step_fused": "recovery",
    "make_dist_spectrum": "recovery",
    "rules_for_arch": "sharding",
    "activate_rules": "sharding",
    "constrain": "sharding",
    "grad_reduce_boundary": "sharding",
}

__all__ = sorted(_LAZY_MODULES) + sorted(_LAZY_SYMBOLS)


def __getattr__(name: str):
    import importlib

    if name in _LAZY_MODULES:
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    if name in _LAZY_SYMBOLS:
        mod = importlib.import_module(f".{_LAZY_SYMBOLS[name]}", __name__)
        # bind every symbol that module provides at once: importing the
        # submodule also sets the package attribute of the module's own
        # name, which must not shadow later symbol lookups
        for other, modname in _LAZY_SYMBOLS.items():
            if modname == _LAZY_SYMBOLS[name]:
                globals()[other] = getattr(mod, other)
        return globals()[name]
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(list(globals()) + list(__all__)))
