"""The shapes a deck gives the band solve, read from a context built on the
host the way every job builds one: padded plane waves per k-point, beta
projectors, coarse FFT box, k-points, bands."""

from __future__ import annotations


def of_deck(deck: dict) -> dict:
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.serve.scheduler import build_job_context

    cfg = load_config(deck)
    ctx = build_job_context(cfg, ".")
    return {"nk": int(ctx.gkvec.num_kpoints), "nb": int(ctx.num_bands),
            "ngk": int(ctx.gkvec.ngk_max),
            "nbeta": int(ctx.beta.num_beta_total),
            "box": [int(d) for d in ctx.fft_coarse.dims]}
