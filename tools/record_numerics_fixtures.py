"""Record the scf_iteration event streams checked in under tests/data/.

tests/test_numerics.py scores the convergence forecaster (obs/forecast.py)
against these fixed trajectories — median iterations-to-converge error and
ledger completeness — so the fixtures must be regenerated (and the
accuracy bar re-checked) whenever a change alters SCF trajectories:

    JAX_PLATFORMS=cpu python tools/record_numerics_fixtures.py

Deck: the tiny silicon deck of tests/test_recovery.py (1 k-point, 8
bands, ultrasoft, density_tol 5e-9), once on the host path and once on
the fused device path.
"""

import json
import os
import tempfile

# mirror tests/conftest.py: the suite runs on a virtual 8-device CPU mesh,
# where the batched band solve (not the single-device Gamma packed-real
# path) is taken — that is the path that carries the numerics ledger and
# engages the fused program, so the fixtures must be recorded on it
import jax

jax.config.update("jax_num_cpu_devices", 8)

from sirius_tpu.obs import events as obs_events
from sirius_tpu.testing import synthetic_silicon_context

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "tests", "data")

DECK = dict(
    gk_cutoff=3.0, pw_cutoff=7.0, ngridk=(1, 1, 1), num_bands=8,
    ultrasoft=True, use_symmetry=False,
    extra_params={"num_dft_iter": 40, "density_tol": 5e-9,
                  "energy_tol": 1e-10},
)

RUNS = (
    ("scf_host_small.jsonl", "off"),
    ("scf_fused_small.jsonl", "auto"),
)


def main() -> None:
    from sirius_tpu.dft.scf import run_scf

    os.makedirs(OUT_DIR, exist_ok=True)
    for name, device_scf in RUNS:
        ctx = synthetic_silicon_context(**DECK)
        ctx.cfg.control.device_scf = device_scf
        with tempfile.TemporaryDirectory() as tmp:
            raw = os.path.join(tmp, "events.jsonl")
            try:
                obs_events.configure(raw)
                res = run_scf(ctx.cfg, ctx=ctx)
            finally:
                obs_events.close()
            assert res["converged"], f"{name}: deck did not converge"
            assert res["recovery"]["recoveries"] == 0
            recs = obs_events.read_events(raw, kind="scf_iteration")
        out = os.path.join(OUT_DIR, name)
        with open(out, "w", encoding="utf-8") as fh:
            for r in recs:
                fh.write(json.dumps(r) + "\n")
        print(f"wrote {out}: {len(recs)} iterations "
              f"(converged in {res['num_scf_iterations']})")


if __name__ == "__main__":
    main()
