"""Sec. 7 compressed-domain deblurring through A = P (C B).

The deployment is one Romberg sensing column, the paper's order-L raster
blur and m kept rows; the frames are synthetic starfields.  The benchmark
makes all of it from the seed; the program gets the sensing column, the
rows and the measurements, composes the blur with its own
``moving_average_blur`` and recovers through a ``RecoveryServer`` whose
plan is the same local lowering as ``build_deblur_plan``'s.
"""

from __future__ import annotations

import gen
import jax
import reference


def build(cfg, key, count):
    """(raw operator data, (count, m) measurements) in one traceable call."""
    h, w, n, m = cfg["height"], cfg["width"], cfg["n"], cfg["m"]
    k_img, k_op, k_rows = jax.random.split(key, 3)
    raw = {"col": gen.romberg_col(k_op, n), "blur": gen.moving_average_col(n, cfg["blur_order"]),
           "omega": gen.subset_indices(k_rows, n, m)}
    frames = jax.vmap(lambda k: gen.starfield(
        k, h, w, cfg["starfield_density"], cfg["starfield_blobs"]))(jax.random.split(k_img, count))
    return raw, reference.sense(reference_operator(cfg, raw), frames.reshape(count, n))


def reference_operator(cfg, raw):
    return reference.operator(cfg["n"], [raw["col"], raw["blur"]], raw["omega"])


def program_operator(cfg, raw):
    from repro.core.circulant import (
        Circulant,
        PartialCirculant,
        compose_sensing_blur,
        moving_average_blur,
    )

    joint = compose_sensing_blur(Circulant.from_first_col(raw["col"]),
                                 moving_average_blur(cfg["n"], cfg["blur_order"]))
    return PartialCirculant(joint, raw["omega"])


def plan_config(cfg):
    from repro.ops import PlanConfig

    return PlanConfig(tail=cfg["tail"])
