"""Where programs run and in which width: backend selection, the compile
cache, the host/device placement rule and the device dtype policy.

Placement rule (stated, not a fallback — it does not depend on whether an
accelerator was found):

  * the band solve on every path, and the fused step behind the batched
    and the packed-real Gamma solve, run on the run's compute devices
    (``devices=`` of run_scf, default ``jax.devices()``), placed there
    explicitly (device_put / NamedSharding);
  * every other stage that goes through ``jnp`` — set-up tables and the
    f64 potential/density/mixing tail of the host paths — runs on
    ``jax.devices("cpu")[0]``: run_scf and SimulationContext.create enter
    ``host_scope()``, so an array made from numpy is never left to the
    default device.

Dtype policy: on a TPU every program of the SCF iteration is 32-bit
(complex64/float32); 64-bit work is host work. A deck that asks for 64-bit
device work on a TPU is refused at set-up (``refuse_64bit_on``).
"""

from __future__ import annotations

import contextlib
import os

import jax
import numpy as np

# --platform choice -> jax_platforms. "tpu" keeps the CPU backend beside the
# chip (chip first = default device): host stages are placed on it
_JAX_PLATFORMS = {"cpu": "cpu", "tpu": "tpu,cpu"}
PLATFORMS = tuple(_JAX_PLATFORMS)


def select_platform(name: str | None) -> None:
    """Select the JAX backend for an entry point's --platform flag and check
    that it is what came up. In a process whose backend is already running
    the request is compared with it instead of re-configured."""
    if name is None:
        return
    if name not in PLATFORMS:
        raise ValueError(f"--platform must be one of {PLATFORMS}, got {name!r}")
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        jax.config.update("jax_platforms", _JAX_PLATFORMS[name])
    got = jax.devices()[0].platform
    if got != name:
        raise RuntimeError(
            f"--platform {name} was asked for but the running JAX backend "
            f"is '{got}' ({jax.devices()[0].device_kind})"
        )


def enable_compile_cache() -> dict:
    """Turn on JAX's persistent compilation cache for an entry point (never
    at import; tests leave it off). Where JAX_COMPILATION_CACHE_DIR is set
    the directory is JAX's own business and none is set in code; otherwise
    the cache lives at the fixed path <checkout>/.jax_cache (the path is
    part of the cache key, so it must not move). Returns
    {"dir": ..., "from_env": bool} for reports."""
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", default_cache_dir())
    # most of a cold run's ~190 programs are small and fast to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return {"dir": jax.config.jax_compilation_cache_dir, "from_env": from_env}


def default_cache_dir() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )


def host_device():
    return jax.devices("cpu")[0]


def host_scope():
    """Default-device scope for host stages (see module docstring)."""
    return jax.default_device(host_device())


@contextlib.contextmanager
def scf_scope():
    """run_scf's scope: host stages on the CPU backend, and float32
    matmuls computed as float32 — a TPU's default for f32 operands is one
    bf16 pass (~3 digits), far below the 1e-5 Ha bar; on the CPU backend
    the setting changes nothing."""
    with host_scope(), jax.default_matmul_precision("highest"):
        yield


def refuse_64bit_on(devices, key: str) -> None:
    """Stop a deck that asks for 64-bit device programs on a TPU: the chip
    has no complex128 and only emulated float64, and neither a silent
    downgrade nor a silent move to the CPU is acceptable."""
    dev = devices[0]
    if dev.platform == "tpu":
        raise ValueError(
            f"{key} asks for 64-bit (complex128) device programs, which "
            f"the {dev.device_kind} ({dev.platform}) does not run; set "
            "parameters.precision_wf = \"fp32\" and "
            "settings.fp32_to_fp64_rms = 0, or run on --platform cpu"
        )


# largest subspace eigenproblem (3 * num_bands) a TPU program partitioned
# over a device mesh may hold: above it the TPU's eigh takes another
# algorithm, and compiling that inside a mesh program crashes the compiler
# (jax 0.9.0 / libtpu 0.0.34: SIGSEGV in Shardy's sharding propagation, a
# check failure under GSPMD; reproduced without a chip for the (k, b) and
# the "g" mesh at 288, passing at 240). One device compiles any size.
TPU_MESH_EIGH_MAX = 256


def refuse_large_subspace_on_tpu_mesh(devices, num_bands: int) -> None:
    """A clear error at set-up instead of a compiler crash that takes the
    whole process (a serving engine) down."""
    dev = devices[0]
    if dev.platform == "tpu" and 3 * num_bands > TPU_MESH_EIGH_MAX:
        raise ValueError(
            f"num_bands = {num_bands}: the band solve's subspace problem "
            f"(3 * num_bands = {3 * num_bands} > {TPU_MESH_EIGH_MAX}) cannot "
            f"be compiled into a program sharded over {len(devices)} "
            f"{dev.device_kind} devices (TPU compiler crash); run this deck "
            "on one device (devices=[one], or --slices = device count)"
        )


def where(x) -> list:
    """[platform, dtype, device ids] of an array, from the array itself
    (a numpy array is host memory: ["host", dtype, []]) — the entries of
    run_scf's result["placement"]."""
    if isinstance(x, jax.Array):
        devs = sorted(x.devices(), key=lambda d: d.id)
        return ["+".join(sorted({d.platform for d in devs})), str(x.dtype),
                [d.id for d in devs]]
    return ["host", str(np.asarray(x).dtype), []]
