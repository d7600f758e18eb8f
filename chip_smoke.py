#!/usr/bin/env python3
"""The quickest proof that the trainer still starts on the chip.

``python chip_smoke.py`` drives the system's main path once through the
entry points a user calls — ``ADAG(...).train(Dataset)`` on the synchronous
plane, ``AsyncADAG`` / ``AsyncAEASGD`` against a parameter-server hub on the
asynchronous one — at the full width of the widest LM the repo runs
(1024-dim, 8 heads x 128, 2048 tokens, 8192 vocabulary, ``LM`` below), with
random weights from a seed and seeded synthetic tokens, then checks every
Pallas kernel against its reference and, on a four-chip host, every program
that spans chips.  It claims no speed: the seconds it prints are
information about bring-up (compile vs steady, cold vs cached), not metrics.

Contract (the driver runs it after every PR):

- exits 0 only if every phase passed, within 1200 s, with no network; any
  exception or failed check propagates to a non-zero exit — there is no
  ``try/except`` around a phase and no "skipped" that still returns 0;
- demands the chip: on any platform but the TPU (``JAX_PLATFORMS=cpu``, a
  libtpu that found no chip) Phase 0 raises, naming the platform it found,
  and nothing else runs.  There is no size switch and no CPU mode here;
  ``tests/test_chip_smoke.py`` calls the phase functions at tiny sizes;
- ONE process uses the chip.  The only child is a ``distkeras-ps`` hub
  daemon, which pins itself to the CPU; it is stopped before its phase ends;
- the last line of stdout is one JSON object with exactly these keys:
  ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``,
  the device as JAX reports it.  It is printed only after every phase
  passed; a failed run prints no result.  The line before it, ``summary
  {...}``, carries the phase records, ends with ``"claim": null`` and is
  for the notes, not for the driver.

Each phase prints one line: its name, the platform, ``device_kind`` and
device count, and its wall seconds split into first call (compile included)
and steady (0 where a phase runs once).
"""

from __future__ import annotations

import gc
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))

# The bring-up check's LM, at these widths as written: nothing is cut.  If
# HBM or host RAM ever forces a cut, cut whole layers HERE — never a width,
# the head size, the sequence or the vocabulary; the depth used is printed
# with the phases and carried in the summary.
LM = dict(vocab_size=8192, model_dim=1024, num_heads=8, num_layers=16,
          max_seq_len=2048)
LM_BATCH = 4     # per replica / per worker
LM_WINDOW = 2    # local steps per communication window
LM_WINDOWS = 3   # communication windows per epoch

# Kernel-vs-reference tolerance, as a share of the reference's largest
# magnitude: kernels take bfloat16 inputs and round p / ds / the outputs to
# bfloat16 (8 bits of mantissa, 2^-9 = 0.2% per rounding, a handful of
# roundings per value); the reference runs in float32 at "highest" matmul
# precision from the same bfloat16 inputs.
KERNEL_TOL = 2e-2
# Fused decode step vs the XLA step: both keep a bfloat16 residual stream
# and differ only in operation order inside a block; logits compared as a
# share of the largest logit.
DECODE_TOL = 2e-2


def _report(phase: str, first_s: float, steady_s: float, **extra) -> dict:
    """Print one phase's line and return it as the summary's record."""
    import jax

    dev = jax.devices()[0]
    rec = {"phase": phase, "platform": dev.platform,
           "device_kind": dev.device_kind, "devices": len(jax.devices()),
           "first_call_s": round(first_s, 3), "steady_s": round(steady_s, 3)}
    rec.update(extra)
    print("phase " + " ".join(f"{k}={v}" for k, v in rec.items()), flush=True)
    return rec


def _token_dataset(rows: int, seq_len: int, vocab: int, seed: int):
    """Seeded synthetic next-token data: ``features`` [rows, L] int32 and
    ``label`` = the same tokens shifted left."""
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.parallel.lm import shift_targets

    tokens = np.random.default_rng(seed).integers(
        0, vocab, size=(rows, seq_len)).astype(np.int32)
    return Dataset({"features": tokens, "label": shift_targets(tokens)})


def _mnist_dataset(rows: int, seed: int):
    """Seeded MNIST-shaped noise with one-hot labels (content does not
    matter to a smoke run; shapes and dtypes do)."""
    from distkeras_tpu.data.dataset import Dataset

    rng = np.random.default_rng(seed)
    return Dataset({
        "features": rng.normal(size=(rows, 28, 28, 1)).astype(np.float32),
        "label": np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=rows)]})


def _moved(before, after) -> bool:
    import jax

    return any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)))


def _mosaic_kernels(lowered) -> list:
    """Names of the Pallas kernels a lowered program hands to Mosaic, one
    per ``tpu_custom_call`` (the kernel function's name rides the call).
    The interpreter and the dense XLA path leave none."""
    text = lowered.as_text()
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    assert len(names) == text.count("@tpu_custom_call"), (
        "a Mosaic custom call without a kernel name: the program text "
        "changed shape, repair this reduction")
    return names


def _check_attention(lowered, seq_len: int, layers: int) -> int:
    """Which attention a lowered LM training program holds.  On the TPU it
    must be what the selector says for this length — the flash kernel — and
    it must be there as Mosaic custom calls, one forward and at least one
    backward per layer: a program that fell back to dense XLA, or ran the
    kernel through the interpreter, has none.  Off the TPU (the CPU tests)
    the selector says dense and there must be none."""
    from distkeras_tpu.ops.attention import attention_impl
    from distkeras_tpu.platform import on_tpu

    kernels = _mosaic_kernels(lowered)
    if on_tpu():
        impl = attention_impl(seq_len, seq_len)
        assert impl == "flash", (
            f"attention selector chose {impl!r} at L={seq_len} on the TPU")
        assert (kernels.count("_fwd_kernel") == layers
                and len(kernels) >= 2 * layers), (
            f"Mosaic kernels in the compiled program: {kernels}; expected "
            f"the flash forward and backward in each of {layers} layers")
    else:
        assert not kernels, f"Mosaic kernels lowered off the TPU: {kernels}"
    return len(kernels)


def lm_model(**lm):
    """The LM at seed 0 with HOST (numpy) parameters, shared by the sync and
    async phases: every trainer then starts from the same bits, and no
    parameter-shaped device array outlives a phase."""
    import jax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import small_lm_spec

    model = Model.init(small_lm_spec(**lm), seed=0)
    # np.array, not asarray: on the CPU backend asarray is a zero-copy view
    # that keeps the device array alive
    return Model(spec=model.spec, params=jax.tree.map(np.array, model.params))


# -- Phase 0 ------------------------------------------------------------------

def phase_devices() -> dict:
    """The devices, which must be TPUs of a kind in the peaks table."""
    import importlib.metadata

    import jax

    from distkeras_tpu.platform import require_tpu

    t0 = time.perf_counter()
    require_tpu()  # raises off the chip, naming the platform found
    return _report("devices", time.perf_counter() - t0, 0.0,
                   jax=jax.__version__,
                   libtpu=importlib.metadata.version("libtpu"))


# -- Phase 1 ------------------------------------------------------------------

def phase_canary(n: int, batch: int = 64, window: int = 4,
                 windows: int = 4) -> dict:
    """ADAG on the README's MNIST-CNN over ``n`` replicas: seconds of chip
    time that catch a dead shard_map/psum path before the wide phases."""
    from distkeras_tpu.models.cnn import mnist_cnn_spec
    from distkeras_tpu.trainers import ADAG

    ds = _mnist_dataset(batch * n * window * windows, seed=0)
    trainer = ADAG(mnist_cnn_spec(), num_workers=n, batch_size=batch,
                   communication_window=window, num_epoch=2,
                   learning_rate=0.05, seed=0)
    before = trainer.model.params
    model = trainer.train(ds, shuffle=False)
    assert len(trainer.history) == 2 * windows, trainer.history
    assert np.isfinite(trainer.history).all(), trainer.history
    assert _moved(before, model.params), "canary center did not move"
    return _report("canary_adag_cnn", trainer.metrics[0]["seconds"],
                   trainer.metrics[1]["seconds"], workers=n)


# -- Phase 2 ------------------------------------------------------------------

def phase_sync_lm(model0, n: int, batch: int = LM_BATCH,
                  window: int = LM_WINDOW, windows: int = LM_WINDOWS) -> dict:
    """The synchronous plane: ``ADAG(lm, num_workers=n).train(Dataset)``
    through ``WindowEngine``, two epochs (the first compiles)."""
    import jax

    from distkeras_tpu.trainers import ADAG

    cfg = model0.spec.config
    seq_len, layers = cfg["max_seq_len"], cfg["num_layers"]
    ds = _token_dataset(batch * n * window * windows, seq_len,
                        cfg["vocab_size"], seed=1)
    trainer = ADAG(model0, loss="sparse_categorical_crossentropy",
                   num_workers=n, batch_size=batch,
                   communication_window=window, num_epoch=2,
                   learning_rate=0.01, seed=0)
    model = trainer.train(ds, shuffle=False)
    assert len(trainer.history) == 2 * windows, trainer.history
    assert np.isfinite(trainer.history).all(), trainer.history
    assert _moved(model0.params, model.params), "sync center did not move"

    # placement and kernel selection, read off the engine that just trained
    engine = trainer.engine
    state = engine.init_state(model)
    for leaf in jax.tree.leaves(state.local):
        on = {shard.device for shard in leaf.addressable_shards}
        assert len(on) == n, (
            f"per-replica local state on {len(on)} devices, expected {n}")
    chunk = next(ds.chunked_epoch(batch * n, ["features", "label"], window=window))
    calls = _check_attention(
        engine.lower_epoch(state, chunk["features"], chunk["label"]),
        seq_len, layers)
    return _report("sync_adag_lm", trainer.metrics[0]["seconds"],
                   trainer.metrics[1]["seconds"], workers=n, layers=layers,
                   mosaic_calls=calls,
                   loss=f"{trainer.history[0]:.3f}->{trainer.history[-1]:.3f}")


# -- Phase 3 ------------------------------------------------------------------

def _live_leaf_devices(shape) -> set:
    """Devices now holding a live float32 device array of ``shape``."""
    import jax

    return {d for a in jax.live_arrays()
            if a.shape == tuple(shape) and a.dtype == np.float32
            for d in a.devices()}


def _async_run(cls, model0, ds, workers: int, batch: int, window: int,
               windows: int, runs: int = 1, **kw):
    """``runs`` trainings of ``cls`` on ONE trainer instance, each from
    ``model0`` (the window program is cached per instance, so the first
    run compiles and the next is steady).  Checks
    what every run must satisfy; returns ``(trainer, centers, seconds)``."""
    import jax

    devices = jax.devices()
    leaf_shape = max((np.shape(l) for l in jax.tree.leaves(model0.params)),
                     key=lambda s: int(np.prod(s)))
    gc.collect()  # trainers hold reference cycles; drop the last phase's
    assert not _live_leaf_devices(leaf_shape), (
        "a parameter-shaped device array outlived an earlier phase; the "
        "placement check below would pass vacuously")
    seen: dict = {}

    def at_window(worker: int, w: int) -> None:
        # the trainer's own window-boundary hook: at its last window a
        # worker's device-resident replica must sit on ITS device
        if w == windows - 1:
            seen[worker] = _live_leaf_devices(leaf_shape)

    trainer = cls(model0, loss="sparse_categorical_crossentropy",
                  num_workers=workers, batch_size=batch,
                  communication_window=window, num_epoch=1,
                  learning_rate=0.01, seed=0, fault_hook=at_window, **kw)
    centers, seconds = [], []
    for _ in range(runs):
        trainer.model, trainer.history = model0, []
        seen.clear()
        t0 = time.perf_counter()
        model = trainer.train(ds, shuffle=False)
        seconds.append(time.perf_counter() - t0)
        assert len(trainer.history) == workers * windows, trainer.history
        assert np.isfinite(trainer.history).all(), trainer.history
        center = jax.tree.map(np.array, model.params)  # one D2H of the center
        del model
        assert _moved(model0.params, center), "async center did not move"
        hub = trainer.parameter_server
        assert hub.num_updates == workers * windows, (
            f"hub applied {hub.num_updates} commits, expected "
            f"{workers * windows}")
        for w in range(workers):
            want = devices[w % len(devices)]
            assert want in seen[w], (
                f"worker {w}: no replica on its device {want}; parameter-"
                f"shaped arrays live on {sorted(d.id for d in seen[w])}")
        centers.append(center)
    return trainer, centers, seconds


def _check_async_program(trainer, model0, batch: int, window: int) -> int:
    """The async window program, lowered for the shapes it trained on."""
    import jax

    cfg = model0.spec.config
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.float32), model0.params)
    opt_state = jax.eval_shape(trainer.optimizer.init, params)
    wx = jax.ShapeDtypeStruct((window, batch, cfg["max_seq_len"]), np.int32)
    return _check_attention(
        trainer._window_fn.lower(params, opt_state, params, wx, wx),
        cfg["max_seq_len"], cfg["num_layers"])


def phase_async_lm(model0, n: int, batch: int = LM_BATCH,
                   window: int = LM_WINDOW, windows: int = LM_WINDOWS) -> list:
    """The asynchronous plane — the paper's hot path: workers pull the
    center from a hub (default socket transport, Python hub), train a
    window on their own device, commit a delta.  ``AsyncADAG`` with ``n``
    workers, then one worker twice from the same seed, whose final centers
    must be BIT-IDENTICAL (the repo's own invariant; on a chip it is also
    what catches an asynchronous host-to-device copy racing the socket
    client's reused landing buffers), then ``AsyncAEASGD`` (the elastic
    rule shares the loop)."""
    import jax

    from distkeras_tpu.runtime.async_trainer import AsyncADAG, AsyncAEASGD

    cfg = model0.spec.config

    def data(workers):
        return _token_dataset(batch * workers * window * windows,
                              cfg["max_seq_len"], cfg["vocab_size"], seed=2)

    out = []
    if n > 1:
        trainer, _, secs = _async_run(AsyncADAG, model0, data(n), n, batch,
                                      window, windows)
        out.append(_report("async_adag_lm", secs[0], 0.0, workers=n,
                           layers=cfg["num_layers"],
                           hub_updates=trainer.parameter_server.num_updates))
        del trainer  # _async_run requires the last run's replicas to be gone
    trainer, centers, secs = _async_run(AsyncADAG, model0, data(1), 1, batch,
                                        window, windows, runs=2)
    for a, b in zip(jax.tree.leaves(centers[0]), jax.tree.leaves(centers[1])):
        assert np.array_equal(a.view(np.uint32), b.view(np.uint32)), (
            "two 1-worker AsyncADAG runs from the same seed ended in "
            "different centers")
    calls = _check_async_program(trainer, model0, batch, window)
    out.append(_report("async_adag_lm_1worker_x2", secs[0], secs[1], workers=1,
                       layers=cfg["num_layers"], mosaic_calls=calls,
                       bit_identical=True))
    del trainer, centers
    trainer, _, secs = _async_run(AsyncAEASGD, model0, data(n), n, batch,
                                  window, windows)
    calls = _check_async_program(trainer, model0, batch, window)
    out.append(_report("async_aeasgd_lm", secs[0], 0.0, workers=n,
                       layers=cfg["num_layers"], mosaic_calls=calls,
                       hub_updates=trainer.parameter_server.num_updates))
    return out


def phase_ps_daemon(n: int, batch: int = 32, window: int = 2,
                    windows: int = 3) -> dict:
    """One process per chip, tested: a real ``distkeras-ps`` daemon started
    as a subprocess WHILE this process holds the chip must come up (it pins
    itself to the CPU), serve a short ``AsyncADAG`` run through
    ``ps_address=``, and exit 0 on SIGTERM."""
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.cnn import mnist_cnn_spec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG

    model0 = Model.init(mnist_cnn_spec(), seed=0)
    ds = _mnist_dataset(batch * n * window * windows, seed=3)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "model.bin")
        with open(path, "wb") as f:
            f.write(model0.serialize())
        proc = subprocess.Popen(
            [sys.executable, "-m", "distkeras_tpu.runtime.launcher",
             "--model", path, "--mode", "adag", "--num-workers", str(n),
             "--host", "127.0.0.1", "--port", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=_ROOT, env=dict(os.environ, PYTHONPATH=_ROOT))
        # a daemon that hangs on the chip its parent holds never prints its
        # banner: the watchdog turns that into EOF on the pipe, not a hang
        watchdog = threading.Timer(120.0, proc.kill)
        watchdog.start()
        try:
            seen = []
            for line in proc.stdout:
                seen.append(line)
                if "listening on" in line:
                    break
            watchdog.cancel()
            assert seen and "listening on" in seen[-1], (
                f"distkeras-ps never came up: {''.join(seen)[-2000:]!r}")
            port = int(seen[-1].rsplit(":", 1)[1])
            t_up = time.perf_counter()
            trainer = AsyncADAG(model0, num_workers=n, batch_size=batch,
                                communication_window=window, num_epoch=1,
                                learning_rate=0.05, seed=0,
                                ps_address=("127.0.0.1", port))
            model = trainer.train(ds, shuffle=False)
            assert len(trainer.history) == n * windows, trainer.history
            assert np.isfinite(trainer.history).all(), trainer.history
            assert _moved(model0.params, model.params), "hub center did not move"
            proc.send_signal(signal.SIGTERM)
            tail = proc.communicate(timeout=60)[0]
        finally:
            watchdog.cancel()
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    assert proc.returncode == 0, (
        f"distkeras-ps exited {proc.returncode} on SIGTERM: {tail[-2000:]!r}")
    return _report("ps_daemon_subprocess", t_up - t0,
                   time.perf_counter() - t_up, workers=n, daemon_exit=0)


# -- Phase 4 ------------------------------------------------------------------

def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all(), "non-finite kernel output"
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _flash_case(name: str, seq_len: int, head_dim: int, heads: int,
                ring_shard: bool, bwd_kernels: tuple) -> dict:
    """Flash forward + backward at the auto-selected blocks against
    ``dense_attention`` in float32 / "highest", all three gradients live.
    ``ring_shard`` runs the kernel the way a live ring step calls it:
    ``flash_attention_with_lse`` with ``q_offset = l_local`` (every key in
    the past), both outputs differentiated.  ``bwd_kernels`` names the
    backward tier this shape must reach: on the TPU the compiled program
    must hold exactly the forward kernel plus those."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.attention import dense_attention
    from distkeras_tpu.ops.flash_attention import (
        flash_attention, flash_attention_with_lse)
    from distkeras_tpu.platform import on_tpu

    rng = np.random.default_rng(seq_len + head_dim)
    shape = (1, seq_len, heads, head_dim)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
               for _ in range(3))
    w_o = jnp.asarray(rng.normal(size=shape), jnp.float32)
    w_lse = jnp.asarray(rng.normal(size=(1, heads, seq_len)), jnp.float32)
    offset = seq_len if ring_shard else 0

    def kernel_loss(q, k, v):
        if ring_shard:
            o, lse = flash_attention_with_lse(q, k, v, causal=True,
                                              q_offset=offset, k_offset=0)
            return jnp.sum(o.astype(jnp.float32) * w_o) + jnp.sum(lse * w_lse), (o, lse)
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) * w_o), (o,)

    def reference_loss(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        o = dense_attention(q, k, v, causal=True, q_offset=offset)
        if ring_shard:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
            lse = jax.nn.logsumexp(scores, axis=-1)  # offset: no key masked
            return jnp.sum(o * w_o) + jnp.sum(lse * w_lse), (o, lse)
        return jnp.sum(o * w_o), (o,)

    run = jax.jit(jax.value_and_grad(kernel_loss, argnums=(0, 1, 2), has_aux=True))
    kernels = sorted(_mosaic_kernels(run.lower(q, k, v)))
    want = sorted(("_fwd_kernel",) + bwd_kernels) if on_tpu() else []
    assert kernels == want, (
        f"{name}: compiled Mosaic kernels {kernels}, expected {want}")
    t0 = time.perf_counter()
    (_, outs), grads = jax.block_until_ready(run(q, k, v))
    t1 = time.perf_counter()
    jax.block_until_ready(run(q, k, v))
    t2 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        (_, ref_outs), ref_grads = jax.jit(jax.value_and_grad(
            reference_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    errs = [_rel_err(a, b) for a, b in zip(outs + grads, ref_outs + ref_grads)]
    assert max(errs) <= KERNEL_TOL, (
        f"{name}: kernel vs reference {errs} exceeds {KERNEL_TOL}")
    return _report(f"kernel_{name}", t1 - t0, t2 - t1, L=seq_len, D=head_dim,
                   mosaic_kernels="+".join(kernels) or "none",
                   max_rel_err=f"{max(errs):.1e}")


def _decode_case(model_dim: int, heads: int, layers: int, vocab: int,
                 prompt_len: int, new_tokens: int) -> dict:
    """The fused single-token decode step against the XLA step: one step's
    logits from the same prefilled cache, then the public generate fn on
    its auto-selected step."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.decode import (
        KVCache, forward_with_cache, fused_token_forward, init_cache,
        make_fused_state, make_generate_fn)
    from distkeras_tpu.models.transformer import small_lm_spec
    from distkeras_tpu.ops.decode_step import (
        resolve_step_impl, round_cache_len, transpose_k_cache)
    from distkeras_tpu.platform import on_tpu

    cache_len = round_cache_len(prompt_len + new_tokens)
    spec = small_lm_spec(vocab_size=vocab, model_dim=model_dim,
                         num_heads=heads, num_layers=layers,
                         max_seq_len=cache_len)
    config = dict(spec.config)
    # the selector's own answer: fused on the TPU means this config is in
    # the auto-selected region, which is the point of the case
    impl = resolve_step_impl(config, 1, cache_len, None)
    assert impl == ("fused" if on_tpu() else "xla"), impl
    params = Model.init(spec, seed=4).params
    prompt = jnp.asarray(np.random.default_rng(4).integers(
        0, vocab, size=(1, prompt_len)), jnp.int32)

    @jax.jit
    def one_step(params, prompt):
        logits, cache = forward_with_cache(
            params, config, prompt, 0, init_cache(config, 1, cache_len),
            last_only=True)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        pos = jnp.asarray(prompt_len, jnp.int32)
        want, _ = forward_with_cache(params, config, tok[:, None], pos, cache)
        got, _, _ = fused_token_forward(
            make_fused_state(params, config), tok, pos,
            transpose_k_cache(cache.k), cache.v)
        return got, want

    kernels = _mosaic_kernels(one_step.lower(params, prompt))
    assert kernels == (["_decode_kernel"] if on_tpu() else []), (
        f"Mosaic kernels in the fused decode step: {kernels}")
    got, want = one_step(params, prompt)
    err = _rel_err(got, want)
    assert err <= DECODE_TOL, f"fused decode logits off by {err} > {DECODE_TOL}"

    generate = make_generate_fn(spec, new_tokens)
    t0 = time.perf_counter()
    toks = np.asarray(generate(params, prompt))
    t1 = time.perf_counter()
    np.asarray(generate(params, prompt))
    t2 = time.perf_counter()
    ref = np.asarray(make_generate_fn(spec, new_tokens, step_impl="xla")(
        params, prompt))
    assert toks.shape == (1, new_tokens)
    assert ((toks >= 0) & (toks < vocab)).all(), "decode produced out-of-vocab ids"
    return _report("kernel_fused_decode_step", t1 - t0, t2 - t1, step=impl,
                   mosaic_kernels="+".join(kernels) or "none",
                   max_rel_err=f"{err:.1e}",
                   greedy_agreement=round(float((toks == ref).mean()), 3))


# (name, L, head_dim, heads, ring_shard, backward kernels): the shapes the
# repo's own selectors send to each tier of the flash backward
# (``flash_attention._make_config`` / ``_fused_q_chunks``) —
#   fused_single   one fused call over one (2048, 2048) block, 48 MiB grant;
#   fused_qchunked the [Lq, D] dq scratch no longer fits: two q-chunks;
#   two_kernel     the dq + dkv fallback, which auto-selection reaches only
#                  at lengths that are no multiple of 512 and long enough
#                  that one chunk's dq scratch passes 12 MiB (13568 = 256 x
#                  53 at head_dim 256 is the lightest such shape);
#   ring_live      ``flash_attention_with_lse`` as a live ring step calls it.
FLASH_CASES = (
    ("flash_fused_single", 2048, 128, 2, False, ("_bwd_fused_kernel",)),
    ("flash_fused_qchunked", 16384, 128, 1, False, ("_bwd_fused_kernel",) * 2),
    ("flash_two_kernel", 13568, 256, 1, False,
     ("_bwd_dq_kernel", "_bwd_dkv_kernel")),
    ("flash_ring_live", 2048, 128, 2, True, ("_bwd_fused_kernel",)),
)
DECODE_CASE = dict(model_dim=128, heads=2, layers=2, vocab=8192,
                   prompt_len=256, new_tokens=512)


def phase_kernels(flash_cases=FLASH_CASES, decode_case=DECODE_CASE) -> list:
    """Every Pallas kernel compiles and agrees with its reference at the
    shapes the selectors send it.  The kernels run as the library selects
    them — Mosaic on the TPU, where each program must hold exactly the
    named kernels; the interpreter in the CPU tests, at tiny shapes."""
    out = [_flash_case(*case) for case in flash_cases]
    out.append(_decode_case(**decode_case))
    return out


# -- Phase 5 ------------------------------------------------------------------

def phase_multichip(n: int, ring_l_local: int = 2048, ring_heads: int = 4,
                    ring_kv_heads: int = 2, ring_head_dim: int = 128,
                    vocab: int = 8192) -> list:
    """Everything that spans chips, on the real devices: the six sections
    of the multichip dry run (each asserts its state is spread over all
    ``n`` devices), then the dp x sp ring once more at a shard long enough
    that ``ring_block_impl`` picks the flash block under ``shard_map``."""
    from __graft_entry__ import assert_spread, lm_ring_step, multichip_sections

    from distkeras_tpu.ops.attention import ring_block_impl
    from distkeras_tpu.platform import on_tpu

    t0 = time.perf_counter()
    multichip_sections(n)
    t1 = time.perf_counter()
    out = [_report("multichip_sections", t1 - t0, 0.0, spread_over=n)]
    block = ring_block_impl(ring_l_local, ring_head_dim)
    assert block == ("flash" if on_tpu() else "dense"), block
    loss, params, lowered = lm_ring_step(
        n, l_local=ring_l_local, model_dim=ring_heads * ring_head_dim,
        num_heads=ring_heads, num_kv_heads=ring_kv_heads, vocab=vocab)
    assert_spread("ring at flash width", params, n)
    kernels = _mosaic_kernels(lowered)
    assert ("_fwd_kernel" in kernels) == (block == "flash"), (
        f"ring block is {block!r} but the step's Mosaic kernels are {kernels}")
    out.append(_report("multichip_ring_flash", time.perf_counter() - t1, 0.0,
                       l_local=ring_l_local, block=block,
                       mosaic_calls=len(kernels), loss=round(float(loss), 3)))
    return out


# -- main ---------------------------------------------------------------------

def result_line() -> str:
    """The line the driver reads: one JSON object with EXACTLY the keys
    ``ok`` and ``device`` (``platform``, ``kind``, ``count``), the device as
    JAX reports it.  Anything else the run learned goes on the ``summary``
    line before it."""
    import jax

    dev = jax.devices()[0]
    return json.dumps({"ok": True,
                       "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(jax.devices())}})


def main() -> None:
    from distkeras_tpu.platform import enable_compile_cache

    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir} "
          f"({'warm' if os.path.isdir(cache_dir) and os.listdir(cache_dir) else 'cold'})",
          flush=True)
    phases = [phase_devices()]

    import jax

    n = len(jax.devices())
    phases.append(phase_canary(n))
    model0 = lm_model(**LM)
    print(f"lm: {LM} depth_used={LM['num_layers']} params="
          f"{sum(int(np.size(l)) for l in jax.tree.leaves(model0.params)):,}",
          flush=True)
    phases.append(phase_sync_lm(model0, n))
    phases.extend(phase_async_lm(model0, n))
    phases.append(phase_ps_daemon(n))
    phases.extend(phase_kernels())
    if n >= 4:
        phases.extend(phase_multichip(n))

    peak_hbm = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    print(f"peak HBM per chip: {[round(b / 2**30, 2) for b in peak_hbm]} GiB",
          flush=True)
    # the bring-up record (information for CHANGES.md), on its own line ...
    print("summary " + json.dumps({
        "seconds": round(time.perf_counter() - t_start, 1),
        "compile_cache": cache_dir,
        "lm_layers": LM["num_layers"],
        "peak_hbm_bytes": peak_hbm,
        "phases": phases,
        "claim": None,
    }), flush=True)
    # ... and the result the driver reads: the LAST line.  Reached only when
    # every phase above returned.
    print(result_line(), flush=True)


if __name__ == "__main__":
    main()
