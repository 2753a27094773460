"""Cost-model-driven plan autotuning: pick a :class:`PlanConfig` instead of
hand-picking one.

The paper's tenfold speedup came from hand-matching the algorithm layout to
the GPU's constraints; the same matching problem reappears here as plan
knobs — rfft, overlap K, tail substrate, batch sharding, the four-step
``n1 x n2`` factorization — all hand-picked per workload even though the
dry-run stack already *models* their cost.  This module closes the loop:

    ``plan(op, mesh, tune=True)``            cost-model pick ("model" mode)
    ``plan(op, mesh, tune="measure")``       + wall-clock the top candidates

Pipeline
--------
1.  **Enumerate** (:func:`candidate_configs`): feasible ``n1 x n2``
    factorizations (the ``_factorize`` default plus caller extras, filtered
    by the transpose-collective divisibility rules), rfft on/off, overlap
    K in {1, 2, 4, 8}, tail substrates available on this backend,
    batch-axis splits the workload's batch actually divides over, and — on
    a factored ``(host, device)`` mesh — flat vs hierarchical exchange
    (``hier_axes``) with per-tier wire dtypes (``inter_wire_dtype``),
    scored by the two-tier ICI/DCN collective model.
2.  **Score** (:func:`score_candidates`): lower each candidate's abstract
    CPADMM iteration block (:meth:`ExecutionPlan.cpadmm_block` from
    ShapeDtypeStructs only — no concrete arrays), walk the compiled HLO with
    :func:`repro.launch.hlo_analysis.analyze_compiled`, and rank by the
    shared roofline + hidden-collective model
    (:func:`repro.launch.roofline.model_block_times` — the same math the
    ``cs_dryrun`` tables print).  Candidates differing only in overlap K
    share one compile: K changes how the transpose's wire time *schedules*
    (chunked collectives), not the payload, so the K sweep is evaluated
    analytically on the K=1 compile — one compile (~seconds) per
    (factorization, rfft, tail, batch split) group instead of per candidate.
3.  **Measure** (``mode="measure"``): wall-clock the top-k model picks as
    concrete blocks (real spectrum, zero state) and let measured time
    override the model's ranking.
4.  **Cache**: the winning config lands in a JSON store
    (:class:`PlanCache`, default ``artifacts/plan_cache.json``, override via
    ``REPRO_PLAN_CACHE``) keyed by (op signature, mesh shape, batch, dtype,
    jax version, backend, pins) — production runs never re-tune.  A
    "measure"-mode entry satisfies both request modes; a "model" entry is
    re-tuned when measurement is asked for.

``COUNTERS`` tracks scored / measured / cache-hit / cache-miss events so
tests (and doubters) can assert a warm cache skips all scoring.

    python -m repro.ops.tune --show     # inspect the cache
    python -m repro.ops.tune --clear    # drop it (e.g. after a jax upgrade)
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.dist.fft import DEVICE_AXIS, HOST_AXIS, MODEL_AXIS, padded_rfft_len
from repro.dist.recovery import DistCpadmmState

from . import spectral
from .plan import (
    PlanConfig,
    _factorize,
    _plan_with_config,
    _transform_extent,
    plan_from_parts,
)
from .prox import is_l1

SDS = jax.ShapeDtypeStruct

DEFAULT_CACHE_PATH = os.path.join("artifacts", "plan_cache.json")
OVERLAPS = (1, 2, 4, 8)
SCORE_ITERS = 8  # iterations in the scored block: enough for the while-loop
#                  trip count to dominate one-off setup, small enough to keep
#                  measure-mode wall-clocks quick
MEASURE_REPEATS = 3

# scored: candidate groups compiled + cost-walked; measured: candidates
# wall-clocked; cache_hits/misses: PlanCache lookups.  Tests assert a warm
# cache leaves scored == measured == 0.
COUNTERS: Dict[str, int] = {
    "scored": 0, "measured": 0, "cache_hits": 0, "cache_misses": 0,
}


def reset_counters() -> None:
    for k in COUNTERS:
        COUNTERS[k] = 0


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------


# paths already warned about this process — a corrupt store quarantines and
# warns once, not on every subsequent lookup
_WARNED_CORRUPT: set = set()


class PlanCache:
    """JSON store of winning configs: ``key -> {config, mode, score, ...}``.

    Writes are atomic (tmp + rename), and :meth:`put` *re-reads the store
    just before the rename* and folds any concurrently-written entries into
    the payload — two tuners racing on different keys both land (the loser
    of a same-key race is overwritten, which is fine: both wrote a winner
    for the same workload).  An unparseable store is never silently treated
    as empty: it is quarantined to ``<path>.corrupt`` with a one-time
    warning, so a corrupted file can't force silent re-tuning forever while
    looking like a working cache.  The default path is overridable with the
    ``REPRO_PLAN_CACHE`` environment variable (tests point it at a tmpdir;
    ops can point it at a shared volume).
    """

    # test seam: called between the tmp write and the pre-replace re-read,
    # where a concurrent tuner's os.replace can land (tests/test_tune.py
    # simulates the race deterministically through it)
    _race_hook = None

    def __init__(self, path: Optional[str] = None):
        self.path = path or os.environ.get("REPRO_PLAN_CACHE", DEFAULT_CACHE_PATH)

    def _quarantine(self, reason: str) -> None:
        import warnings

        corrupt = f"{self.path}.corrupt"
        try:
            os.replace(self.path, corrupt)
        except OSError:
            corrupt = "<unmovable>"
        if self.path not in _WARNED_CORRUPT:
            _WARNED_CORRUPT.add(self.path)
            warnings.warn(
                f"plan cache {self.path} is unreadable ({reason}); "
                f"quarantined to {corrupt} and starting a fresh store — "
                f"delete the .corrupt file once inspected",
                RuntimeWarning,
                stacklevel=3,
            )

    def _load(self) -> Dict[str, dict]:
        try:
            with open(self.path) as f:
                raw = f.read()
        except OSError:  # missing store: legitimately empty
            return {}
        if not raw.strip():
            return {}
        try:
            data = json.loads(raw)
        except ValueError as e:
            self._quarantine(f"invalid JSON: {e}")
            return {}
        if not isinstance(data, dict):
            self._quarantine(f"top-level JSON is {type(data).__name__}, not dict")
            return {}
        return data

    def get(self, key: str) -> Optional[dict]:
        return self._load().get(key)

    def put(self, key: str, entry: dict) -> None:
        data = self._load()
        data[key] = entry
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        # unique per *call*, not just per process: two racing puts in one
        # process (threads, or the reentrant test seam) must not share a tmp
        fd, tmp = tempfile.mkstemp(
            prefix=os.path.basename(self.path) + ".tmp.", dir=d or "."
        )
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        if self._race_hook is not None:
            self._race_hook()
        # close the read-modify-write window: another tuner may have replaced
        # the store since our load above — re-read and merge (our key wins
        # its own slot) so concurrent winners are never silently dropped
        latest = self._load()
        if any(k not in data for k in latest):
            latest.update(data)
            with open(tmp, "w") as f:
                json.dump(latest, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    def clear(self) -> None:
        try:
            os.remove(self.path)
        except FileNotFoundError:
            pass

    def entries(self) -> Dict[str, dict]:
        return self._load()


def cache_key(op, mesh, batch: Optional[int], pins: Optional[dict]) -> str:
    """Everything the winning config is conditional on, flattened to a str.

    Op signature (type, n, m) rather than op identity: two partial
    circulants of the same size tune identically — the knobs depend on
    shapes, not spectrum values.  jax version + backend are in the key
    because the cost of a lowering is a property of the compiler.
    """
    sig = (type(op).__name__, getattr(op, "n", None), getattr(op, "m", None))
    axes = tuple(zip(mesh.axis_names, (mesh.shape[a] for a in mesh.axis_names)))
    dtype = str(getattr(getattr(op, "circ", op), "col", jnp.zeros(0)).dtype)

    def _jsonable(v):
        if hasattr(v, "to_dict") and hasattr(v, "tag"):  # a Prox pin
            return v.to_dict()
        return list(v) if isinstance(v, tuple) else v

    pin_s = json.dumps(
        {k: _jsonable(v) for k, v in sorted((pins or {}).items())}
    )
    return "|".join([
        f"op={sig}", f"mesh={axes}", f"batch={batch}", f"dtype={dtype}",
        f"jax={jax.__version__}", f"backend={jax.default_backend()}",
        f"pins={pin_s}",
    ])


# ---------------------------------------------------------------------------
# candidate enumeration
# ---------------------------------------------------------------------------


def _feasible_factorizations(
    n: int, p: int, rfft: bool, extra: Sequence[Tuple[int, int]]
) -> List[Tuple[int, int]]:
    """The ``_factorize`` near-sqrt default plus caller extras, deduped and
    filtered by the transpose-collective divisibility rules."""
    out: List[Tuple[int, int]] = []
    try:
        out.append(_factorize(n, None, None, p, rfft))
    except ValueError:
        pass
    for n1, n2 in extra:
        if n1 * n2 != n or n1 % p:
            continue
        if not rfft and n2 % p:
            continue
        if (n1, n2) not in out:
            out.append((n1, n2))
    return out


def candidate_configs(
    op,
    mesh,
    pins: Optional[dict] = None,
    batch: Optional[int] = None,
    extra_factorizations: Sequence[Tuple[int, int]] = (),
) -> List[PlanConfig]:
    """Enumerate the feasible candidate space, honoring ``pins``.

    A pin (any individual plan knob passed alongside ``tune=``) collapses
    that knob's axis of the space to the pinned value; ``n1``/``n2`` pins
    replace the factorization sweep.
    """
    pins = dict(pins or {})
    axis_name = pins.get("axis_name")
    if axis_name is None:
        # a hierarchical mesh (compat.make_hier_mesh) implies the factored
        # transform axis; the tuner then races flat-layout vs two-stage
        # hierarchical exchanges over it (hier_axes sweep below)
        if HOST_AXIS in mesh.axis_names and DEVICE_AXIS in mesh.axis_names:
            axis_name = (HOST_AXIS, DEVICE_AXIS)
        else:
            axis_name = MODEL_AXIS
    if isinstance(axis_name, (list, tuple)):
        axis_name = tuple(axis_name)
    t_axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    missing = [a for a in t_axes if a not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"axis_name {axis_name!r} not in mesh axes {mesh.axis_names}"
        )
    p = math.prod(mesh.shape[a] for a in t_axes)
    circ = getattr(op, "circ", op)
    n = circ.n

    rffts = (pins["rfft"],) if "rfft" in pins else (False, True)
    overlaps = (pins["overlap"],) if "overlap" in pins else OVERLAPS
    if "tail" in pins:
        tails: Tuple[str, ...] = (pins["tail"],)
    elif jax.default_backend() == "tpu" and is_l1(pins.get("prox")):
        tails = ("jnp", "pallas")
    else:
        # the pallas tail interprets (slowly) off-TPU, and its fused kernel
        # applies only the l1 prior
        tails = ("jnp",)
    fuseds = (pins["fused"],) if "fused" in pins else (True,)
    # default wire sweep stops at bf16: same exponent range as fp32, so the
    # plan()-side precision guard essentially always accepts it; fp16 (more
    # mantissa, tiny range) is opt-in via a pin — overflow on large-magnitude
    # spectra would make the guard demote it back to fp32 anyway
    wires = (pins["wire_dtype"],) if "wire_dtype" in pins else ("fp32", "bf16")

    # hier_axes sweep: on a factored transform axis, race the flat layout
    # (one monolithic all-to-all over both tiers) against the two-stage
    # hierarchical exchange — the two-tier cost model splits them apart
    if isinstance(axis_name, tuple):
        extents = tuple(mesh.shape[a] for a in axis_name)
        if "hier_axes" in pins:
            ha = pins["hier_axes"]
            hier_opts: Tuple[Any, ...] = (
                tuple(ha) if ha is not None else None,
            )
        else:
            hier_opts = (None, extents)
    else:
        ha = pins.get("hier_axes")
        hier_opts = (tuple(ha) if ha is not None else None,)
    # a non-fp32 inter wire only exists on hierarchical candidates (the flat
    # exchange has no separate inter-host hop) — pinning it drops flat
    if pins.get("inter_wire_dtype", "fp32") != "fp32":
        hier_opts = tuple(h for h in hier_opts if h is not None)
        if not hier_opts:
            raise ValueError(
                "inter_wire_dtype pin needs a hierarchical candidate space "
                "(a (host, device) mesh, or hier_axes pinned non-None)"
            )

    def _inter_wires(hier) -> Tuple[str, ...]:
        if hier is None:
            return ("fp32",)
        if "inter_wire_dtype" in pins:
            return (pins["inter_wire_dtype"],)
        return ("fp32", "bf16")  # same bf16-not-fp16 default as `wires`

    if "batch_axis" in pins:
        batch_axes: List[Any] = [pins["batch_axis"]]
    else:
        batch_axes = [None]
        other = tuple(a for a in mesh.axis_names if a not in t_axes)
        if other and batch:
            sizes = math.prod(mesh.shape[a] for a in other)
            if sizes > 1 and batch % sizes == 0:
                batch_axes.append(other if len(other) > 1 else other[0])

    out: List[PlanConfig] = []
    for rfft in rffts:
        if "n1" in pins or "n2" in pins:
            try:
                facs = [_factorize(n, pins.get("n1"), pins.get("n2"), p, rfft)]
            except ValueError:
                continue
        else:
            facs = _feasible_factorizations(n, p, rfft, extra_factorizations)
        for n1, n2 in facs:
            for tail in tails:
                for fused in fuseds:
                    for ba in batch_axes:
                        for wire in wires:
                            for hier in hier_opts:
                                for iw in _inter_wires(hier):
                                    for K in overlaps:
                                        out.append(PlanConfig(
                                            rfft=rfft, overlap=K, tail=tail,
                                            fused=fused, batch_axis=ba,
                                            n1=n1, n2=n2,
                                            axis_name=axis_name,
                                            wire_dtype=wire,
                                            hier_axes=hier,
                                            inter_wire_dtype=iw,
                                            prox=pins.get("prox"),
                                        ))
    if not out:
        raise ValueError(
            f"no feasible plan candidates for n={n} over a {p}-device "
            f"{axis_name!r} axis with pins {pins}"
        )
    return out


# ---------------------------------------------------------------------------
# scoring (abstract lowering + shared cost model)
# ---------------------------------------------------------------------------


def _group_key(cfg: PlanConfig) -> tuple:
    """Candidates equal up to overlap share one compile (see module header).

    ``wire_dtype`` is part of the key: demoting the wire changes the
    compiled collective's payload bytes (the HLO the cost walk reads), not
    just its schedule — so fp32 and bf16 wires never share a compile.  So
    are ``hier_axes`` and ``inter_wire_dtype``: the hierarchical exchange
    compiles to different collectives entirely (intra-tier all-to-all +
    inter-tier collective-permutes vs one monolithic all-to-all).  ``prox``
    too: a non-elementwise prior swaps the fused one-shard_map block for the
    hybrid core+global-tail lowering, and even an elementwise swap changes
    the tail math the walk prices."""
    return (cfg.rfft, cfg.n1, cfg.n2, cfg.tail, cfg.fused, cfg.batch_axis,
            cfg.axis_name, cfg.wire_dtype, cfg.hier_axes, cfg.inter_wire_dtype,
            cfg.prox)


def _compile_group(mesh, cfg: PlanConfig, batch: int, iters: int):
    """Lower + compile one candidate group's abstract CPADMM block at K=1."""
    pl = plan_from_parts(
        mesh, config=dataclasses.replace(cfg, overlap=1)
    )
    block = pl.cpadmm_block(iters)
    p = _transform_extent(mesh, cfg.axis_name)
    ncols = padded_rfft_len(cfg.n2, p) if cfg.rfft else cfg.n2
    spec_s = SDS((cfg.n1, ncols), jnp.complex64)
    diag_s = SDS((cfg.n1, cfg.n2), jnp.float32)
    real_b = SDS((batch, cfg.n1, cfg.n2), jnp.float32)
    state_s = DistCpadmmState(*(real_b,) * 5)
    return block.lower(spec_s, spec_s, diag_s, real_b, state_s).compile()


def _dcn_bytes(cost, cfg: PlanConfig, mesh) -> float:
    """Cross-host wire bytes of one compiled block, for the two-tier model.

    Hierarchical plans put exactly the inter-host hop into
    ``collective-permute`` ops (repro.dist.fft two-stage exchange), so their
    DCN bytes read straight off the HLO walk.  A *flat* exchange over a
    factored ``(host, device)`` axis spanning more than one host is a single
    monolithic all-to-all whose every byte crosses the boundary — its whole
    all-to-all payload is charged to DCN.  Single-axis plans have no host
    tier and ride ICI only (0.0 — the bit-for-bit fallback).
    """
    if cfg.hier_axes is not None:
        return float(cost.collective_bytes.get("collective-permute", 0.0))
    if isinstance(cfg.axis_name, tuple) and mesh.shape[cfg.axis_name[0]] > 1:
        return float(cost.collective_bytes.get("all-to-all", 0.0))
    return 0.0


def score_candidates(
    mesh, candidates: Sequence[PlanConfig], batch: int, iters: int = SCORE_ITERS
) -> List[Tuple[float, PlanConfig, dict]]:
    """Rank candidates by modeled block time, ascending.

    One compile + HLO walk per overlap-group; the overlap sweep is analytic
    (:func:`model_block_times` on the shared K=1 cost).  Cross-host bytes
    (:func:`_dcn_bytes`) are charged at the DCN rate of the mesh's device
    kind (``roofline.peaks_for``; a kind with no peak entry raises) — this
    is what splits flat from hierarchical candidates on a multi-host mesh.
    Ties break
    toward the *simpler* config — lower overlap, then rfft off — so a mesh
    where a knob is cost-neutral (e.g. a 1-device axis, where collectives
    vanish) keeps the defaults rather than picking complexity for nothing.
    """
    from repro.launch.hlo_analysis import analyze_compiled
    from repro.launch.roofline import model_block_times, peaks_for

    peaks = peaks_for(mesh.devices.flat[0].device_kind)
    costs: Dict[tuple, Any] = {}
    scored: List[Tuple[float, PlanConfig, dict]] = []
    for cfg in candidates:
        gk = _group_key(cfg)
        if gk not in costs:
            compiled = _compile_group(mesh, cfg, batch, iters)
            costs[gk] = analyze_compiled(compiled)
            COUNTERS["scored"] += 1
        times = model_block_times(
            costs[gk], cfg.overlap,
            dcn_bytes=_dcn_bytes(costs[gk], cfg, mesh), peaks=peaks,
        )
        scored.append((times["modeled_total_s"], cfg, times))
    scored.sort(key=lambda t: (t[0], t[1].overlap, t[1].rfft, t[1].describe()))
    return scored


# ---------------------------------------------------------------------------
# measurement (concrete top-k wall-clock)
# ---------------------------------------------------------------------------


def measure_config(
    op, mesh, cfg: PlanConfig, batch: int, iters: int = SCORE_ITERS,
    repeats: int = MEASURE_REPEATS,
) -> float:
    """Wall-clock one candidate's concrete CPADMM block: real spectrum and
    mask via the plan lowering, zero measurements/state (the *cost* of an
    iteration does not depend on the data values), min of ``repeats`` runs
    after a warmup."""
    pl = _plan_with_config(op, mesh, cfg)
    block = pl.cpadmm_block(iters)
    rho = sigma = jnp.float32(0.01)  # cpadmm_block's scoring defaults
    b_spec = spectral.gram_inverse_spectrum(pl.spec2d, rho, sigma)
    d_diag = jnp.where(pl.mask2d > 0, 1.0 / (1.0 + rho), 1.0 / rho).astype(
        jnp.float32
    )
    zeros = jnp.zeros((batch, pl.n1, pl.n2), jnp.float32)
    state = DistCpadmmState(*(zeros,) * 5)
    block(pl.spec2d, b_spec, d_diag, zeros, state).z.block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        block(pl.spec2d, b_spec, d_diag, zeros, state).z.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    COUNTERS["measured"] += 1
    return best


# ---------------------------------------------------------------------------
# the tuner entry point
# ---------------------------------------------------------------------------


def tuned_config(
    op,
    mesh,
    mode: str = "model",
    batch: Optional[int] = None,
    pins: Optional[dict] = None,
    cache: Optional[PlanCache] = None,
    top_k: int = 2,
    score_iters: int = SCORE_ITERS,
    extra_factorizations: Sequence[Tuple[int, int]] = (),
) -> PlanConfig:
    """Pick the :class:`PlanConfig` for (op, mesh, batch) — cached.

    ``mode="model"`` ranks by the HLO cost model alone; ``mode="measure"``
    additionally wall-clocks the top ``top_k`` model picks and lets measured
    time decide.  ``pins`` (individual plan knobs) restrict the candidate
    space; they are part of the cache key, so pinned and unpinned tunes
    never collide.  With ``mesh=None`` there is nothing distributed to tune:
    the pins (validated) are the answer.
    """
    if mode not in ("model", "measure"):
        raise ValueError(f"tune mode must be 'model' or 'measure', got {mode!r}")
    pins = dict(pins or {})
    if mesh is None:
        return PlanConfig(**pins).validate(distributed=False)

    cache = cache if cache is not None else PlanCache()
    key = cache_key(op, mesh, batch, pins)
    hit = cache.get(key)
    if hit is not None and (mode != "measure" or hit.get("mode") == "measure"):
        COUNTERS["cache_hits"] += 1
        return PlanConfig.from_dict(hit["config"])
    COUNTERS["cache_misses"] += 1

    cands = candidate_configs(
        op, mesh, pins=pins, batch=batch,
        extra_factorizations=extra_factorizations,
    )
    bench_batch = batch or 1
    scored = score_candidates(mesh, cands, batch=bench_batch, iters=score_iters)
    best_score, best_cfg, best_detail = scored[0]
    entry: dict = {
        "config": best_cfg.to_dict(),
        "mode": "model",
        "modeled_total_s": best_score,
        "candidates": len(cands),
        "detail": {k: v for k, v in best_detail.items()},
    }
    if mode == "measure":
        # wall-clock the best candidate of the top_k best *distinct compile
        # groups* (not the raw top_k, which can be K-sweep variants of one
        # group): the model's close calls between groups are exactly what
        # measurement is for
        picks: List[PlanConfig] = []
        seen_groups: set = set()
        for _, cfg, _ in scored:
            gk = _group_key(cfg)
            if gk in seen_groups:
                continue
            seen_groups.add(gk)
            picks.append(cfg)
            if len(picks) >= top_k:
                break
        measured = []
        for cfg in picks:
            measured.append(
                (measure_config(op, mesh, cfg, bench_batch, score_iters), cfg)
            )
        measured.sort(key=lambda t: t[0])
        best_wall, best_cfg = measured[0]
        entry.update(
            config=best_cfg.to_dict(), mode="measure", measured_s=best_wall,
            measured_top_k=[
                {"config": c.to_dict(), "s": s} for s, c in measured
            ],
        )
    cache.put(key, entry)
    return best_cfg


# ---------------------------------------------------------------------------
# cache CLI
# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(
        description="Inspect or clear the plan-autotune cache."
    )
    ap.add_argument("--cache", default=None, help="cache path override")
    ap.add_argument("--show", action="store_true", help="print entries")
    ap.add_argument("--clear", action="store_true", help="delete the store")
    args = ap.parse_args(argv)
    cache = PlanCache(args.cache)
    if args.clear:
        cache.clear()
        print(f"cleared {cache.path}")
        return
    entries = cache.entries()
    print(f"{cache.path}: {len(entries)} cached plan(s)")
    for key, entry in sorted(entries.items()):
        cfg = PlanConfig.from_dict(entry["config"])
        score = entry.get("measured_s", entry.get("modeled_total_s"))
        print(f"  [{entry['mode']}] {cfg.describe()}  score={score:.3e}")
        print(f"    key: {key}")


if __name__ == "__main__":
    main()
