"""``chip_smoke.py`` off the chip: it must refuse to run here, and its phase
functions must still work — called tiny, on the CPU mesh, with the Pallas
kernels in interpret mode (the library's own choice on this backend).

The on-chip guide's "make the command run here first": a refactor that
breaks a phase shows up in tier-1, not in the next chip-tool call.  What a
CPU run cannot show — Mosaic accepting the kernels, placement over real
chips, host-to-device races — is exactly what the script asserts only when
``on_tpu()``.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TINY_LM = dict(vocab_size=64, model_dim=32, num_heads=2, num_layers=1,
                max_seq_len=16)

# what a fresh interpreter reports after calling the compile-cache helper
_CACHE_PROBE = ("import json, jax; from distkeras_tpu.platform import "
                "enable_compile_cache as e; r = e(); "
                "print(json.dumps([r, jax.config.jax_compilation_cache_dir]))")


@pytest.fixture(scope="module")
def tiny_lm():
    """``chip_smoke.lm_model`` with the Flax init jitted: the eager init
    costs several seconds of per-op CPU compiles, which tier-1 cannot
    afford (the slow cells below go through ``lm_model`` itself)."""
    import jax
    import numpy as np

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import small_lm_spec

    spec = small_lm_spec(**_TINY_LM)
    params = jax.jit(lambda: spec.init_params(seed=0))()
    return Model(spec=spec, params=jax.tree.map(np.array, params))


@pytest.fixture(scope="module")
def fresh_interpreters(tmp_path_factory):
    """Four fresh interpreters, run concurrently (each pays a JAX import):
    ``python chip_smoke.py`` held to the CPU, and the compile-cache probe
    once with ``JAX_COMPILATION_CACHE_DIR`` set and twice without."""
    given_dir = str(tmp_path_factory.mktemp("given_cache"))
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base["PYTHONPATH"] = _REPO
    probe = [sys.executable, "-c", _CACHE_PROBE]
    cmds = {"smoke": ([sys.executable, os.path.join(_REPO, "chip_smoke.py")],
                      dict(base, JAX_PLATFORMS="cpu")),
            "given": (probe, dict(base, JAX_COMPILATION_CACHE_DIR=given_dir)),
            "unset_a": (probe, base), "unset_b": (probe, base)}
    procs = {name: subprocess.Popen(cmd, cwd=_REPO, env=env, text=True,
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE)
             for name, (cmd, env) in cmds.items()}
    deadline = time.monotonic() + 120  # for the four together
    try:
        out = {name: (p.communicate(timeout=max(deadline - time.monotonic(), 1.0)),
                      p.returncode)
               for name, p in procs.items()}
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
    out["given_dir"] = given_dir
    return out


def test_device_phase_refuses_the_cpu_and_names_it():
    with pytest.raises(RuntimeError, match=r"no TPU.*platform 'cpu'"):
        chip_smoke.phase_devices()


def test_script_exits_nonzero_without_a_result_off_the_chip(fresh_interpreters):
    """The driver's first check: in a sandbox ``python chip_smoke.py`` fails
    within seconds and prints no result line."""
    (stdout, stderr), returncode = fresh_interpreters["smoke"]
    assert returncode != 0
    assert "platform 'cpu'" in stderr
    assert '"ok"' not in stdout


def test_result_line_holds_exactly_the_keys_the_driver_reads():
    """The driver refuses a last line with any key besides ``ok`` and
    ``device`` {``platform``, ``kind``, ``count``}; the phase records ride
    the ``summary`` line before it."""
    import jax

    line = chip_smoke.result_line()
    assert "\n" not in line
    dev = jax.devices()[0]
    assert json.loads(line) == {
        "ok": True, "device": {"platform": dev.platform,
                               "kind": dev.device_kind,
                               "count": len(jax.devices())}}


def test_canary_phase_tiny():
    rec = chip_smoke.phase_canary(2, batch=4, window=2, windows=2)
    assert rec["platform"] == "cpu" and rec["workers"] == 2


def test_sync_lm_phase_tiny(tiny_lm):
    rec = chip_smoke.phase_sync_lm(tiny_lm, 2, batch=2, window=2, windows=2)
    # dense attention off the TPU: no Mosaic kernel may be in the program
    assert rec["mosaic_calls"] == 0 and rec["layers"] == 1


def test_async_lm_phase_tiny():
    tiny_lm = chip_smoke.lm_model(**_TINY_LM)
    recs = chip_smoke.phase_async_lm(tiny_lm, 2, batch=2, window=2, windows=2)
    assert [r["phase"] for r in recs] == [
        "async_adag_lm", "async_adag_lm_1worker_x2", "async_aeasgd_lm"]
    assert recs[1]["bit_identical"] is True
    assert recs[0]["hub_updates"] == recs[2]["hub_updates"] == 4


def test_ps_daemon_phase_tiny():
    rec = chip_smoke.phase_ps_daemon(2, batch=4, window=2, windows=2)
    assert rec["daemon_exit"] == 0


def test_kernel_phase_tiny():
    recs = chip_smoke.phase_kernels(
        flash_cases=(("flash_tiny", 64, 16, 1, False, ()),
                     ("flash_ring_tiny", 64, 16, 1, True, ())),
        decode_case=dict(model_dim=128, heads=2, layers=1, vocab=64,
                         prompt_len=4, new_tokens=4))
    assert all(r["mosaic_kernels"] == "none" for r in recs)  # interpreted
    assert recs[-1]["step"] == "xla"  # what auto-selection picks off the TPU


def test_mosaic_kernel_names_are_read_from_a_tpu_lowering():
    """The reduction the chip run relies on, checked against the installed
    JAX: lowered FOR the TPU (no chip needed to lower) the flash forward and
    fused backward appear as named Mosaic custom calls."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=False).astype(jnp.float32))

    x = jax.ShapeDtypeStruct((1, 256, 1, 128), jnp.bfloat16)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(x, x, x).lower(
        lowering_platforms=("tpu",))
    assert sorted(chip_smoke._mosaic_kernels(lowered)) == [
        "_bwd_fused_kernel", "_fwd_kernel"]


@pytest.mark.slow  # 41 s alone: all six multi-device sections compile on the CPU mesh
def test_multichip_phase_tiny():
    recs = chip_smoke.phase_multichip(4, ring_l_local=8, ring_heads=4,
                                      ring_kv_heads=2, ring_head_dim=16,
                                      vocab=128)
    assert recs[0]["spread_over"] == 4
    assert recs[1]["block"] == "dense" and recs[1]["mosaic_calls"] == 0


# -- the compile cache helper --------------------------------------------------

def test_compile_cache_placed_from_outside_or_at_the_fixed_path(fresh_interpreters):
    def probe(name):
        (stdout, stderr), returncode = fresh_interpreters[name]
        assert returncode == 0, stderr
        return json.loads(stdout.strip().splitlines()[-1])

    # env set: the helper reports it and sets nothing — JAX read it itself
    assert probe("given") == [fresh_interpreters["given_dir"]] * 2
    # env unset: exactly <checkout>/.jax_cache, the same in every process
    want = os.path.join(_REPO, ".jax_cache")
    assert probe("unset_a") == probe("unset_b") == [want, want]


def test_compile_cache_helper_leaves_an_env_setting_alone(monkeypatch):
    import jax

    from distkeras_tpu.platform import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # untouched


# -- one process per chip ------------------------------------------------------

def test_ps_daemon_pins_cpu_before_it_deserializes(monkeypatch, tmp_path):
    """``distkeras-ps`` must be off the accelerator BEFORE ``Model.
    deserialize`` runs its Flax init on the default backend."""
    import distkeras_tpu.platform as platform
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.runtime import launcher

    calls = []
    monkeypatch.setattr(platform, "pin_cpu_devices",
                        lambda n: calls.append(("pin", n)))

    def deserialize(blob):
        calls.append(("deserialize", len(blob)))
        raise SystemExit("stop before serving")

    monkeypatch.setattr(Model, "deserialize", staticmethod(deserialize))
    model_file = tmp_path / "model.bin"
    model_file.write_bytes(b"blob")
    with pytest.raises(SystemExit, match="stop before serving"):
        launcher.main(["--model", str(model_file)])
    assert calls == [("pin", 1), ("deserialize", 4)]
