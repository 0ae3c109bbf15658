"""What decides ``correct``: every counted job went down the configuration's
expected path in 32-bit types on the compute platform, converged, and its
total energy lies within the configuration's bar of the stored f64 reference
of its geometry. Each number is reported beside its limit."""

from __future__ import annotations

WIDTHS_32 = ("float32", "complex64")
FUSED_STAGES = ("fused_step", "density", "mixing", "potential")


def placement_fault(pl: dict, platform: str, path: str,
                    chips: int = 1) -> str | None:
    """None when the job's placement record shows ``path`` with 32-bit types
    on ``platform`` (chip_smoke.check_placement) and the band solve on
    ``chips`` distinct devices, else what is wrong."""
    if not pl:
        return "no placement record"
    if pl.get("path") != path:
        return f"path {pl.get('path')!r}, expected {path!r}"
    stages = ("band_solve",) + (FUSED_STAGES if path == "batched+fused" else ())
    for s in stages:  # entries are [platform, dtype, device ids]
        where = pl.get(s)
        if not where or where[0] != platform or where[1] not in WIDTHS_32:
            return f"stage {s} ran at {where}, expected 32-bit on {platform}"
    used = len(set(pl["band_solve"][2]))
    if used != chips:
        return f"band solve on {used} device(s), the cell has {chips}"
    return None


def judge(rec: dict, refs: dict, atoms: int, tol_per_atom: float,
          platform: str, path: str, chips: int = 1) -> dict:
    """Fill ``abs_de_ha``, ``de_limit_ha``, ``ok`` and ``why`` into a job's
    record (in place) and return it."""
    limit = tol_per_atom * atoms
    rec["de_limit_ha"] = limit
    rec["abs_de_ha"] = None
    why = []
    result = rec.get("result")
    if rec.get("error") or result is None:
        why.append(rec.get("error") or "no result")
    else:
        if not result.get("converged"):
            why.append(f"not converged in {result.get('num_scf_iterations')} iterations")
        ref = refs.get(str(rec["geometry"]))
        if ref is None:  # an error, never a pass
            why.append(f"no stored reference for geometry {rec['geometry']}")
        else:
            rec["energy_ref_ha"] = ref["energy_total_ha"]
            rec["abs_de_ha"] = abs(result["energy"]["total"] - ref["energy_total_ha"])
            if not rec["abs_de_ha"] <= limit:  # also catches NaN
                why.append(f"|dE| {rec['abs_de_ha']:.3e} Ha over {limit:.1e} Ha")
        fault = placement_fault(result.get("placement"), platform, path, chips)
        if fault:
            why.append(fault)
    rec["ok"] = not why
    rec["why"] = "; ".join(why)
    return rec
