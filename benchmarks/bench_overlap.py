"""Overlap sweep: chunked-transpose FFT pipeline vs the monolithic transpose.

Two complementary measurements per K:

  * measured: wall time of the distributed rfft forward+inverse pair with
    ``overlap=K`` on the in-process mesh — on one device the collective is
    free, so this isolates the *overhead* of chunking (extra reshuffles,
    K small FFDs instead of one big one).  The overlap win itself cannot
    show on one host device; the dry-run models it on the production mesh.
  * modeled: the hidden-collective fraction at the production mesh shape
    (n=4096x4096, model=16, batch/device=1), same window model as
    ``repro.launch.cs_dryrun``: per chunk, min(a2a time, stage-1 HBM time)
    of the remaining K-1 chunks hides behind compute.
"""

from __future__ import annotations

import jax

from repro.kernels.wire_pack.ops import wire_itemsize

# bandwidths shared with the dry-run's roofline so the two models can
# never diverge; the production shape is a v5e pod
from repro.launch.roofline import PEAKS, V5E

from .common import emit, pick, time_fn

N1, N2 = pick((512, 512), (32, 16))
OVERLAPS = (1, 2, 4, 8)

# production-shape model constants (mirrors launch/cs_dryrun)
PROD_N1 = PROD_N2 = 4096
PROD_P = 16


def _hidden_fraction_model(k: int, wire_dtype: str = "fp32") -> float:
    """Hidden-collective fraction of one forward rfft transform at the
    production shape: (k-1)/k of the wire hides, capped by the stage-1
    local window (HBM-bound row-rfft of the device's block).  The payload
    itemsize comes from the configured wire dtype (2 real planes per
    complex element), not a hardcoded complex64."""
    nf_pad = -(-(PROD_N2 // 2 + 1) // PROD_P) * PROD_P
    elem_bytes = 2 * wire_itemsize(wire_dtype)  # split-complex (re, im)
    a2a_bytes = (PROD_N1 // PROD_P) * nf_pad * elem_bytes
    stage1_bytes = (PROD_N1 // PROD_P) * (PROD_N2 * 4 + nf_pad * 8)  # r + w
    wire_s = a2a_bytes / PEAKS[V5E].ici_bw
    window_s = stage1_bytes / PEAKS[V5E].hbm_bw
    hidden = min((k - 1) / k * wire_s, window_s)
    return hidden / wire_s


def main() -> None:
    from repro.dist.compat import make_mesh
    from repro.dist.fft import layout_2d, make_distributed_rfft

    mesh = make_mesh((1,), ("model",))
    n = N1 * N2
    x = layout_2d(jax.random.normal(jax.random.PRNGKey(0), (n,)), N1, N2)

    t_mono = None
    for k in OVERLAPS:
        rfwd, rinv = make_distributed_rfft(mesh, N1, N2, overlap=k)
        roundtrip = jax.jit(lambda a: rinv(rfwd(a)))
        t = time_fn(roundtrip, x)
        t_mono = t if k == 1 else t_mono
        emit(
            f"overlap_rfft_n{n}_k{k}",
            t,
            f"chunk_overhead={t / t_mono:.2f}x;"
            f"prod_hidden_frac={_hidden_fraction_model(k):.2f}",
        )

    # wire-compressed variant of the same sweep: bf16 payload halves the
    # modeled wire time, so more of it hides at the same K (the measured
    # column again isolates pack+chunk overhead — one device, free wire)
    for k in OVERLAPS:
        rfwd, rinv = make_distributed_rfft(
            mesh, N1, N2, overlap=k, wire_dtype="bf16"
        )
        roundtrip = jax.jit(lambda a: rinv(rfwd(a)))
        t = time_fn(roundtrip, x)
        emit(
            f"overlap_rfft_bf16wire_n{n}_k{k}",
            t,
            f"overhead_vs_fp32wire_k1={t / t_mono:.2f}x;"
            f"prod_hidden_frac={_hidden_fraction_model(k, 'bf16'):.2f}",
        )


if __name__ == "__main__":
    main()
