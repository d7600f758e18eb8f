"""Which device this process runs on — a LEAF module (imports nothing
from this package).

Kept import-light on purpose: callers (tests/conftest.py, ``chip_smoke.py``,
``benchmark/run.py``, the examples, ``distkeras-ps``) must be able to choose the
platform before any other module gets a chance to touch a JAX backend.  The
package ``__init__`` is lazy (PEP 562) so ``from distkeras_tpu.platform
import pin_cpu_devices`` executes only this file.

The installation is plain JAX with libtpu: the TPU is the default backend
where a chip is visible, ``JAX_PLATFORMS=cpu`` holds a process to the CPU,
and when libtpu cannot initialise JAX falls back to the CPU with only a
warning.  That silent fallback is why everything that means to run on the
chip goes through :func:`require_tpu` (or, for the examples,
:func:`select_platform`) and everything that must stay off it — the tests,
the hub daemon — through :func:`pin_cpu_devices`.  One process owns the
chip: a child that needs it while the parent holds it fails or hangs.
"""

from __future__ import annotations

import os

# Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.  JAX reports
# a v5e chip as "TPU v5 lite".  A kind that is not here is an error, never
# a default: an MFU or roofline share over a guessed peak is not a number.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def device_peaks(device_kind: str) -> dict:
    """The ``DEVICE_PEAKS`` row for ``device_kind``; raises on a kind the
    table does not hold."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)} — add the row with its source to "
            f"distkeras_tpu.platform.DEVICE_PEAKS") from None


def require_tpu() -> list:
    """``jax.devices()``, after refusing anything but a TPU whose kind is
    in the peaks table.  No retry and no CPU pin: a measurement or smoke
    run that finds no chip fails, naming the platform it found."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX came up on platform {devices[0].platform!r} "
            f"({len(devices)} x {devices[0].device_kind}); this command "
            f"runs on the chip only")
    device_peaks(devices[0].device_kind)
    return devices


def on_tpu() -> bool:
    """True on the TPU backend, False on the CPU — what every kernel
    selector keys on (Pallas ``interpret``, flash vs dense, fused vs XLA
    decode step).  Any other backend name raises: a chip whose backend
    reports an unexpected name must not quietly run its kernels through
    the interpreter or the XLA reference."""
    import jax

    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"unexpected JAX backend {backend!r}: kernels are selected for "
            f"'tpu' (Mosaic) or 'cpu' (interpreter / XLA reference) only")
    return backend == "tpu"


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is touched here.  Otherwise the cache goes to the fixed path
    ``<checkout>/.jax_cache`` derived from this file's location — never a
    temporary, pid or time-stamped directory, because the path is part of
    the cache key and a directory that moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pin_cpu_devices(n: int) -> None:
    """Pin this process to an ``n``-device virtual CPU platform.

    The one shared copy of the CPU-simulation recipe: the tests, the
    examples' ``--cpu N``, the multichip dry run and the hub daemon (which
    never needs an accelerator) all use it.  ``JAX_PLATFORMS=cpu`` in the
    environment does the same for the platform; this also gives the
    virtual device count, and works when the variable is not set.

    - The platform is pinned through ``jax.config`` BEFORE the first
      ``jax.devices()`` call, so the TPU backend is never initialised — on
      a host whose chip another process holds that initialisation fails
      or hangs.
    - ``--xla_force_host_platform_device_count`` is read once at CPU client
      creation; if a backend already exists (wrong platform or too few
      devices) the only fix is ``clear_backends()`` + ``jax_num_cpu_devices``
      (which takes precedence over the XLA flag).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if len(devs) < n or devs[0].platform != "cpu":
        from jax.extend.backend import clear_backends

        clear_backends()
        jax.config.update("jax_num_cpu_devices", n)
        devs = jax.devices()
    if len(devs) < n or devs[0].platform != "cpu":
        raise RuntimeError(f"could not materialize {n} CPU devices; have {devs}")


def select_platform(cpu_devices: int = 0) -> None:
    """Prologue of every example ``main()``: place the compile cache, then
    either pin a ``cpu_devices``-wide virtual CPU platform (``--cpu N``) or
    — the user asked for the chip by omitting ``--cpu`` — print the
    platform JAX came up on and refuse to continue on ``cpu``."""
    enable_compile_cache()
    if cpu_devices:
        pin_cpu_devices(cpu_devices)
        return
    import jax

    devices = jax.devices()
    print(f"platform: {devices[0].platform} "
          f"({len(devices)} x {devices[0].device_kind})", flush=True)
    if devices[0].platform == "cpu":
        raise SystemExit(
            "no accelerator: JAX came up on the cpu platform (libtpu found "
            "no chip, or JAX_PLATFORMS=cpu is set); pass --cpu N to run on "
            "a virtual CPU mesh on purpose")
