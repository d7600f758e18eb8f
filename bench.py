"""Benchmark harness. Prints exactly ONE JSON line on stdout, always, and
exits NON-ZERO unless every leg ran on the TPU without an error.

Headline metric (``BASELINE.json``): MNIST-CNN training samples/sec/chip —
    {"metric": "...", "value": N, "unit": "...", "vs_baseline": N, ...}

Extra keys on the same object (diagnostics + secondary benches):
    platform      — "tpu"; any other platform is a failure, there is no
                    CPU fallback (``platform.require_tpu``)
    lm            — TransformerLM train-step bench (tokens/sec + MFU) at
                    2k and 8k tokens, flash attention
    attn          — flash-vs-dense attention kernel microbench (fwd+bwd
                    ms/step and speedup) at 2k and 8k tokens
    error         — fatal failure note; value stays 0.0 but the line still
                    parses.  A leg's own failure is recorded as that leg's
                    ``{"error": ...}``; either one makes the exit code 1

``vs_baseline``: the reference publishes no benchmark numbers, so the
ratio is against the recorded best of THIS repo (bench_baseline.json).
First run: 1.0.

Data content doesn't affect throughput, so MNIST-shaped synthetic tensors
stand in for the real dataset in offline environments.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback


def _peak_flops(device_kind: str) -> float:
    """bf16 peak FLOP/s of one chip, from the one sourced table
    (``distkeras_tpu.platform.DEVICE_PEAKS``); raises on a kind it does
    not hold — an MFU over a guessed peak is not a number."""
    from distkeras_tpu.platform import device_peaks

    return device_peaks(device_kind)["bf16_flops"]


def _init_backend() -> str:
    """The platform the bench runs on, which is the TPU or nothing: raises
    when JAX came up on anything else (no retry, no CPU pin — a CPU number
    under a device metric's name is worse than no number)."""
    from distkeras_tpu.platform import require_tpu

    return require_tpu()[0].platform


# v5e sweet spot from the 2026-07-30 in-program sweep (see _bench_mnist_cnn),
# re-confirmed under bf16 (2026-07-31: 1024 -> 1.543M, 2048 -> 1.523M,
# 4096 -> 1.037M); the single source for both the bench config and the
# reported metadata
_MNIST_BATCH = 1024
# round-5 headline config: the compute_dtype="bfloat16" policy (bf16
# activations over f32 params, f32 logits — models/cnn.py) measured
# 1.35x the f32 headline (1.543M vs 1.140M samples/s/chip, device time).
# NOTE the history: round 2 measured "bf16 slower" and kept f32 — that
# experiment cast the whole model; the activations-only policy keeps the
# optimizer/params f32 and lets XLA fuse the casts into the convs.  The
# f32 number stays recorded next to the headline (mnist_cnn_f32).
_MNIST_DTYPE = "bfloat16"

# bump whenever the headline measurement itself changes (batch size, dispatch
# structure, timing source, ...); vs_baseline is only computed against a
# matching tag.  v3-device reads the program's on-device duration from a
# profiler trace (same shift the decode legs made in round 4): the v2 wall
# number swung +-10% run to run — the official round-4 captures of
# the SAME build read 956k and then 888k — while device time repeats to
# ~0.01% (v5e, 2026-07-31, not re-measured).  Falls back to the v2 wall tag when the trace has no module
# events (CPU runs), so a wall number can never ratio against the
# device-keyed baseline.
# v4: the headline CONFIG changed (bf16 compute_dtype policy, round 5) —
# per the rule above, the tag bumps so a v3-f32 record can never produce a
# bogus cross-config ratio in either direction
_METHODOLOGY = "in-program-multi-epoch-v4-device-bf16"
_METHODOLOGY_WALL = "in-program-multi-epoch-v2"


def _bench_mnist_cnn(batch_size: int = _MNIST_BATCH, num_batches: int = 200, reps: int = 3,
                     repeat: int = 3, compute_dtype=None):
    """Headline number: MNIST-CNN scan-epoch training throughput.
    Returns (samples_per_sec_per_chip, methodology_tag).

    All ``reps`` epochs run inside ONE compiled program (outer lax.scan over
    the inner per-batch scan): the round-1 bench (one dispatch per epoch,
    host sync between) measured ~50-100 ms of host time per dispatch, not
    the chip — moving the loop in-program took the same model from ~400k
    to ~1M samples/sec (v5e, 2026-07-30, not re-measured).
    batch 1024 is the measured v5e sweet spot (sweep 2026-07-30, in-program:
    512->765k, 1024->999k, 2048->565k, 4096->520k samples/sec; re-held
    under bf16 in round 5).  ``compute_dtype`` selects the model's
    mixed-precision policy: "bfloat16" (the round-5 headline) measured
    1.35x f32 — the round-2 "bf16 slower" finding applied to a
    whole-model cast, not the activations-only policy.  Timed on DEVICE
    time
    (median of ``repeat`` in-trace runs; see ``_device_time_ms``), wall
    fallback off-TPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import lax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.cnn import mnist_cnn_spec
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.parallel.engine import make_minibatch_step

    spec = mnist_cnn_spec(compute_dtype=compute_dtype)
    model = Model.init(spec, seed=0)
    optimizer = optax.sgd(0.01, momentum=0.9)
    mini = make_minibatch_step(spec.apply_fn(), get_loss("categorical_crossentropy"), optimizer)

    @jax.jit
    def multi_epoch(params, opt_state, xs, ys):
        def epoch(carry, _):
            carry, losses = lax.scan(mini, carry, (xs, ys))
            return carry, losses[-1]

        (params, opt_state), last = lax.scan(
            epoch, (params, opt_state), None, length=reps)
        return params, opt_state, last

    rng = np.random.default_rng(0)
    xs_d = jnp.asarray(rng.normal(size=(num_batches, batch_size, 28, 28, 1)).astype(np.float32))
    ys_d = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=(num_batches, batch_size))])

    params = jax.tree.map(jnp.array, model.params)
    opt_state = optimizer.init(params)

    samples = reps * num_batches * batch_size
    # _device_time_ms warms up (compile + one full pass) outside the
    # trace, then returns the median on-device duration of `repeat`
    # in-trace runs — or the wall median when no module events exist
    # (CPU), which the returned tag records so the ratio logic can
    # refuse to compare it against a device-keyed baseline
    ms, _, source = _device_time_ms(
        lambda: multi_epoch(params, opt_state, xs_d, ys_d)[2],
        reps=repeat)
    method = _METHODOLOGY if source == "device" else _METHODOLOGY_WALL
    return samples / (ms / 1e3) / jax.device_count(), method


def _bench_lm(seq_len: int, batch: int, *, model_dim: int = 512, num_heads: int = 4,
              num_layers: int = 8, vocab: int = 8192, steps: int = 10,
              remat: bool = False):
    """TransformerLM fwd+bwd train step: tokens/sec + MFU (flash attention).

    ``num_heads`` is a real lever, not plumbing: at fixed model_dim the
    VPU softmax work per score is constant while the per-score matmul
    FLOPs scale with head_dim, so 4 heads x 128 head_dim halves the
    attention VPU-to-MXU ratio of 8 x 64 at identical total FLOPs — the
    round-3 hypothesis for why the 512-dim legs cap near 0.38 MFU while
    1024-dim (head_dim 128) reaches 0.47.

    The loss path is the framework's fused unembed+CE
    (``ops.losses.unembed_cross_entropy``, same as ``make_lm_train_step``):
    the unembed matmul runs in bf16 at MXU rate and the [B, L, V] f32
    logits tensor is never materialized — on v5e this moved the 2k-token
    step from 0.28 to ~0.4 MFU by itself (round-3 sweep).

    MFU counts the matmul FLOPs the model *requires*: 6·T·P_matmul for the
    dense projections + unembed (fwd 2·T·P, bwd 2x) plus the causal
    attention term 6·n_layers·B·L²·E (4·B·L²·E fwd halved by causality,
    times 3 for fwd+bwd) — the standard PaLM-style accounting.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import small_lm_spec
    from distkeras_tpu.ops.losses import lm_token_cross_entropy
    from distkeras_tpu.parallel.lm import shift_targets

    spec = small_lm_spec(vocab_size=vocab, model_dim=model_dim, num_heads=num_heads,
                         num_layers=num_layers, max_seq_len=seq_len, remat=remat)
    model = Model.init(spec, seed=0)
    module = spec.build()
    opt = optax.sgd(0.01)

    def loss_fn(params, tok, tgt):
        ce = lm_token_cross_entropy(module, params, tok, tgt)
        return ce[:, :-1].mean()

    # the step loop lives INSIDE the compiled program: per-dispatch host
    # time would otherwise dominate and the bench would measure the host,
    # not the chip
    @jax.jit
    def run(params, opt_state, tok, tgt):
        def body(carry, _):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, tok, tgt)
            updates, opt_state = opt.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), None, length=steps)
        return params, opt_state, losses

    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, vocab, size=(batch, seq_len)), dtype=jnp.int32)
    tgt = jnp.asarray(shift_targets(np.asarray(tok)))
    params = jax.tree.map(jnp.array, model.params)
    opt_state = opt.init(params)

    def run_all():
        _, _, losses = run(params, opt_state, tok, tgt)
        return losses

    # on-device duration (see _device_time_ms): at 8k the ~10-110 ms of
    # per-dispatch host time was 1-6% of the 1.75 s wall (v5e, 2026-07-31)
    # — enough to misstate MFU
    dev_ms, _, source = _device_time_ms(run_all, reps=2)
    dt = dev_ms / 1e3

    tokens_per_step = batch * seq_len
    e = model_dim
    p_matmul = 12 * e * e * num_layers + e * vocab
    flops_per_step = (6 * tokens_per_step * p_matmul
                      + 6 * num_layers * batch * seq_len * seq_len * e)
    sec_per_step = dt / steps
    peak = _peak_flops(jax.devices()[0].device_kind)
    return {
        "seq_len": seq_len,
        "batch": batch,
        "tokens_per_sec": round(tokens_per_step / sec_per_step, 1),
        "ms_per_step": round(sec_per_step * 1e3, 2),
        "timing": source,
        "mfu": round(flops_per_step / sec_per_step / peak, 4),
    }


def _ab_kernel_ms(flash_loss, dense_loss, steps: int, q, k, v):
    """Shared flash-vs-dense A/B harness for the attn and ring legs:
    per-step on-device ms for both impls via ``_grad_scan_runner`` +
    ``_device_time_ms``.  Returns (flash_ms, dense_ms, timing, speedup);
    ``speedup`` is None when the two sides resolved to DIFFERENT timing
    sources (one device, one wall fallback) — a wall/device ratio would
    fold the host dispatch share into a "kernel speedup"."""
    def one(loss):
        run = _grad_scan_runner(loss, steps)
        ms, _, src = _device_time_ms(run, q, k, v, reps=2)
        return ms / steps, src

    f_ms, f_src = one(flash_loss)
    d_ms, d_src = one(dense_loss)
    timing = "device" if f_src == d_src == "device" else "wall"
    speedup = round(d_ms / f_ms, 2) if f_src == d_src else None
    return f_ms, d_ms, timing, speedup


def _grad_scan_runner(loss_fn, steps: int):
    """Jitted fwd+bwd timing harness shared by the attn and ring benches:
    ``steps`` gradient steps inside ONE program (lax.scan), feeding each
    step's q-grad back into q so the body stays loop-variant (XLA cannot
    hoist it) and keeping ALL THREE grads live — without the gk/gv sum XLA
    DCEs the dv matmul out of the dense backward while the fused flash VJP
    can't be partially eliminated, which would skew the comparison."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    grad_fn = jax.grad(loss_fn, argnums=(0, 1, 2))

    @jax.jit
    def run(q, k, v):
        def body(q, _):
            gq, gk, gv = grad_fn(q, k, v)
            return q + 1e-6 * gq, (jnp.sum(gk) + jnp.sum(gv)).astype(jnp.float32)

        q, sums = lax.scan(body, q, None, length=steps)
        return sums

    return run


def _bench_attn(seq_len: int, *, batch: int = 2, heads: int = 8, head_dim: int = 64,
                steps: int = 50):
    """Kernel microbench: Pallas flash vs XLA dense attention, fwd+bwd.

    On-device timing (``_device_time_ms``) like every other kernel leg —
    the wall variant of this bench is where the round-3 "flash needs
    B*L >= 16k tokens" misread came from."""
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.ops.attention import dense_attention
    from distkeras_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    shape = (batch, seq_len, heads, head_dim)
    q, k, v = (jnp.asarray(rng.normal(size=shape) * 0.1, dtype=jnp.bfloat16)
               for _ in range(3))

    def loss_of(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v, causal=True).astype(jnp.float32))

        return loss

    flash_ms, dense_ms, timing, speedup = _ab_kernel_ms(
        loss_of(flash_attention), loss_of(dense_attention), steps, q, k, v)
    return {
        "seq_len": seq_len,
        "flash_ms": round(flash_ms, 3),
        "dense_ms": round(dense_ms, 3),
        "flash_speedup": speedup,
        "timing": timing,
    }


def _trace_jit_durs(trace_dir: str):
    """All on-device ``jit_*`` XLA-module event durations (ms) found in a
    ``jax.profiler.trace`` output directory — the single home of the trace
    parsing shared by ``_device_time_ms`` (median-of-reps) and
    ``_bench_async`` (sum over a whole run)."""
    import glob
    import gzip
    import os as _os

    durs = []
    for tf in glob.glob(_os.path.join(trace_dir, "**", "*.trace.json.gz"),
                        recursive=True):
        with gzip.open(tf, "rt") as fh:
            data = json.load(fh)
        for ev in data.get("traceEvents", []):
            if ev.get("ph") == "X" and ev.get("name", "").startswith("jit_"):
                durs.append(ev["dur"] / 1e3)
    return durs


def _device_time_ms(fn, *args, reps: int = 3):
    """(median ms per call, wall spread, source) for ``fn(*args)`` where
    ``source`` is ``"device"`` (profiler module events) or ``"wall"`` (the
    fallback) — callers must surface the source in their methodology tag
    so a wall fallback can never match a device-keyed baseline.

    Wall-clock carried a ~10-110 ms per-dispatch host cost that swung run
    to run (v5e, 2026-07-31; cause not established) — for sub-second
    programs (every decode leg) that noise DOMINATED the round-3 numbers
    and fired a false regression tripwire (fp 0.78x).  The on-device
    duration of
    the program's ``jit_*`` XLA-module event, read from a
    ``jax.profiler.trace``, is stable to ~0.01% run-to-run (measured
    2026-07-31: three reps of the decode program within 5us of each
    other), so per-leg ``vs_baseline`` tripwires key on device time.
    Falls back to wall time when the trace has no module events (CPU
    interpret paths in tests)."""
    import tempfile

    import jax
    import numpy as np

    def once():
        r = fn(*args)
        np.asarray(r[0] if isinstance(r, tuple) else r)

    once()  # compile + warm outside the trace
    walls = []
    with tempfile.TemporaryDirectory() as td:
        with jax.profiler.trace(td):
            for _ in range(reps):
                t0 = time.perf_counter()
                once()
                walls.append(time.perf_counter() - t0)
        durs = _trace_jit_durs(td)
    import statistics

    wall_med = statistics.median(walls)
    spread = round((max(walls) - min(walls)) / wall_med, 3) if wall_med else 0.0
    # the timed program is the section's only dispatch, so its reps are the
    # largest module events in the trace
    durs = sorted(durs)[-reps:]
    if len(durs) < reps:
        # the caller must TAG the number as wall time — a wall number under
        # a device-keyed baseline would fire the exact false tripwire this
        # helper exists to kill
        return wall_med * 1e3, spread, "wall"
    return statistics.median(durs), spread, "device"


def _train_decode_pair(spec, draft_spec, vocab: int, *, steps: int = 300,
                       batch: int = 16, seq: int = 256, seed: int = 0):
    """Teach the decode target AND draft the same predictable next-token
    structure so speculative acceptance is realistic (round-3 verdict task
    1b: a random-weights draft agrees with a random-weights target ~never,
    which measures nothing).

    The task: tokens follow a fixed random successor map with 10% uniform
    noise — the optimal greedy predictor is the map itself, learnable by
    both the 8-layer target and the small draft, so their greedy argmaxes
    agree wherever both learned the map.  Returns (target_params,
    draft_params); training runs as one compiled scan per model."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.ops.losses import lm_token_cross_entropy

    rng = np.random.default_rng(seed)
    succ = rng.permutation(vocab)
    toks = np.empty((steps, batch, seq), np.int64)
    cur = rng.integers(0, vocab, (steps, batch))
    for t in range(seq):
        toks[:, :, t] = cur
        nxt = succ[cur]
        noise = rng.random((steps, batch)) < 0.10
        cur = np.where(noise, rng.integers(0, vocab, (steps, batch)), nxt)
    tok_d = jnp.asarray(toks, jnp.int32)

    from distkeras_tpu.parallel.lm import shift_targets
    tgt_d = jnp.asarray(shift_targets(toks).astype(np.int32))

    def fit(spec_, seed_):
        module = spec_.build()
        model = Model.init(spec_, seed=seed_)
        opt = optax.adam(1e-3)

        def loss_fn(params, tok, tgt):
            return lm_token_cross_entropy(module, params, tok, tgt)[:, :-1].mean()

        @jax.jit
        def run(params, opt_state):
            def body(carry, data):
                params, opt_state = carry
                loss, grads = jax.value_and_grad(loss_fn)(params, *data)
                updates, opt_state = opt.update(grads, opt_state, params)
                return (optax.apply_updates(params, updates), opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), (tok_d, tgt_d))
            return params, losses

        params, losses = run(model.params, opt.init(model.params))
        np.asarray(losses)
        return params

    return fit(spec, seed_=0), fit(draft_spec, seed_=1)


def _bench_decode(*, batch: int = 8, prompt_len: int = 128, new_tokens: int = 512,
                  model_dim: int = 512, num_heads: int = 8, num_layers: int = 8,
                  vocab: int = 8192, reps: int = 3, train_steps: int = 300):
    """KV-cache autoregressive decode throughput (greedy), tokens/sec —
    fp (bf16 activations, f32 weights), int8 (weight-only quantized
    params), and speculative (small draft proposing k=4 tokens per target
    verification, with TRAINED target+draft so acceptance is real).

    The whole generation (prefill + ``new_tokens`` scanned single-token
    steps) is one compiled program.  Round-3 verdict weak #1: min-of-2
    WALL timing over ~0.1s generations swung ±30-60% run to run
    (a ~10-110ms per-dispatch host cost on sub-second programs) and fired a
    false 0.78x regression tripwire — every leg now reports the ON-DEVICE
    median (``_device_time_ms``; run-to-run stable to ~0.01%) plus the
    wall ``spread`` as a noise indicator.  Measured decomposition
    (v5e, 2026-07-31, fp_b1): 45.5ms device in a 156ms wall.

    Speculative legs come in both shapes: batch-1 (compare against
    fp_b1_trained) and full-batch lockstep-commit (compare against
    fp_trained — the batched plain decode of the SAME trained weights).
    b1 decode at this scale is bound by per-op launch overhead, NOT
    weight bandwidth (storing weights bf16/int8 moves b1 <3%), which is
    why the draft's value is cutting sequential target steps, not
    FLOPs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.decode import make_generate_fn
    from distkeras_tpu.models.speculative import make_speculative_generate_fn
    from distkeras_tpu.models.transformer import small_lm_spec
    from distkeras_tpu.ops.quantize import quantize_params

    max_len = prompt_len + new_tokens + 16
    spec = small_lm_spec(vocab_size=vocab, model_dim=model_dim, num_heads=num_heads,
                         num_layers=num_layers, max_seq_len=max_len)
    model = Model.init(spec, seed=0)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, vocab, (batch, prompt_len)), jnp.int32)
    key = jax.random.PRNGKey(0)

    sources = []

    def leg(timing, n=new_tokens, **extra):
        ms, spread, source = timing
        sources.append(source)
        dt = ms / 1e3
        return {"tokens_per_sec": round(n / dt, 1),
                "ms_per_token": round(dt / n * 1e3, 4),
                "wall_spread": spread, **extra}

    out = {"batch": batch, "prompt_len": prompt_len, "new_tokens": new_tokens}

    # label every plain-decode leg with the step impl that actually ran
    # (same resolver as make_generate_fn's auto path): if a config change
    # ever flips a leg onto the fused kernel, the record says so instead
    # of silently switching the speedup denominators
    from distkeras_tpu.ops.decode_step import resolve_step_impl
    step_b = resolve_step_impl(spec.config, batch, prompt_len + new_tokens, None)
    step_b1 = resolve_step_impl(spec.config, 1, prompt_len + new_tokens, None)

    fn = make_generate_fn(spec, new_tokens)
    out["fp"] = leg(_device_time_ms(fn, model.params, prompt, key, reps=reps),
                    n=batch * new_tokens, step_impl=step_b)

    qparams = quantize_params(model.params)
    out["int8"] = leg(_device_time_ms(fn, qparams, prompt, key, reps=reps),
                      n=batch * new_tokens, step_impl=step_b)

    out["fp_b1"] = leg(_device_time_ms(fn, model.params, prompt[:1], key, reps=reps),
                       step_impl=step_b1)

    # high-throughput serving pair at batch 64: plain bf16-cache decode
    # saturates near 33k tok/s (per-row KV reads grow linearly with
    # batch) while the int8 KV cache (QKVCache) halves that traffic and
    # un-saturates the curve — 62.5k tok/s, 1.91x at b64 (v5e device
    # time, 2026-07-31; crossover ~b12: int8 LOSES at b1/b8 where the
    # quantize-on-write op overhead outweighs the read savings).  Greedy
    # agreement on the trained pair measured 100% over 2048 tokens.
    big = 64
    prompt_big = jnp.asarray(rng.integers(0, vocab, (big, prompt_len)),
                             jnp.int32)
    out["fp_b64"] = leg(
        _device_time_ms(fn, model.params, prompt_big, key, reps=reps),
        n=big * new_tokens,
        step_impl=resolve_step_impl(spec.config, big,
                                    prompt_len + new_tokens, None))
    qfn = make_generate_fn(spec, new_tokens, quantize_cache=True)
    out["kv_int8_b64"] = leg(
        _device_time_ms(qfn, model.params, prompt_big, key, reps=reps),
        n=big * new_tokens, kv_cache="int8")

    # GQA at serving batch (round 5): 8 query heads sharing 2 KV heads
    # cuts the cache — and with it the per-step read traffic that
    # saturates batched decode — 4x; composed with the int8 cache the
    # KV bytes drop 8x vs the bf16 MHA baseline.  Same 512-dim/8L
    # architecture otherwise; weight content doesn't affect throughput
    # (measured for the trained/untrained pairs above)
    gqa_kv = max(1, num_heads // 4)
    gqa_spec = small_lm_spec(vocab_size=vocab, model_dim=model_dim,
                             num_heads=num_heads, num_kv_heads=gqa_kv,
                             num_layers=num_layers, max_seq_len=max_len)
    gqa_model = Model.init(gqa_spec, seed=0)
    gfn = make_generate_fn(gqa_spec, new_tokens)
    out["fp_b64_gqa"] = leg(
        _device_time_ms(gfn, gqa_model.params, prompt_big, key, reps=reps),
        n=big * new_tokens, kv_heads=gqa_kv)
    qgfn = make_generate_fn(gqa_spec, new_tokens, quantize_cache=True)
    out["kv_int8_b64_gqa"] = leg(
        _device_time_ms(qgfn, gqa_model.params, prompt_big, key, reps=reps),
        n=big * new_tokens, kv_heads=gqa_kv, kv_cache="int8")

    # speculative leg: TRAINED 8-layer target + small draft on a
    # predictable task (see _train_decode_pair) — acceptance_rate is part
    # of the leg; a random-weights pair would report ~0 acceptance and the
    # number would mean nothing.  k=8/draft 2L-128 from the 2026-07-31
    # device-time sweep: 29.9k tok/s vs fp_b1's 11.2k (2.66x) with the
    # XLA draft; the fused Pallas draft step (ops/decode_step.py, auto-
    # selected at batch 1 for draft-sized models) lifted it to 40.6k
    # (3.6x) the same day — the leg records which draft step ran
    draft_dim = min(128, model_dim)
    draft_spec = small_lm_spec(vocab_size=vocab, model_dim=draft_dim,
                               num_heads=min(2, num_heads), num_layers=2,
                               max_seq_len=max_len)
    t_params, d_params = _train_decode_pair(spec, draft_spec, vocab,
                                            steps=train_steps)
    k = 8
    # the SAME resolver the generate fn's auto path runs (imported above),
    # so the recorded label can never drift from the implementation that
    # produced the number
    draft_impl = resolve_step_impl(
        draft_spec.config, 1, prompt_len + new_tokens + k + 1, None)
    sfn = make_speculative_generate_fn(spec, draft_spec, new_tokens, k=k,
                                       with_stats=True)
    toks, iters = sfn(t_params, d_params, prompt[:1])
    np.asarray(toks)
    # the while-loop commits new_tokens - 1 tokens (the first comes from
    # the prefill, before the loop), m + 1 per round -> mean m =
    # (n-1)/iters - 1.  The final round may be truncated by the n bound,
    # so clamp to [0, 1] rather than report a boundary artifact
    acceptance = ((new_tokens - 1) / max(int(iters), 1) - 1.0) / k
    acceptance = min(max(acceptance, 0.0), 1.0)
    out["speculative_b1"] = leg(
        _device_time_ms(sfn, t_params, d_params, prompt[:1], reps=reps),
        draft_layers=2, draft_dim=draft_dim, k=k, draft_step=draft_impl,
        acceptance_rate=round(float(acceptance), 3), trained=True)
    # the same trained target through the PLAIN decode path: the apples-to-
    # apples denominator for the speculative speedup claim (weights don't
    # change plain-decode cost, but report it measured, not assumed)
    out["fp_b1_trained"] = leg(_device_time_ms(fn, t_params, prompt[:1], key,
                                               reps=reps), step_impl=step_b1)
    spec_ratio = (out["speculative_b1"]["tokens_per_sec"]
                  / out["fp_b1_trained"]["tokens_per_sec"])
    out["speculative_speedup_vs_fp_b1"] = round(spec_ratio, 3)

    # batched speculative (lockstep min-prefix commit, models/speculative
    # .py): the same draft/verify program over the full batch — at batch 8
    # /k=8 the committed-token rate is 2.6x the plain batched decode on
    # the trained pair (v5e 2026-07-31; k=12 reached 3.2x the same day —
    # k stays 8 here to match the b1 leg)
    toks, iters = sfn(t_params, d_params, prompt)
    np.asarray(toks)
    acc_b = ((new_tokens - 1) / max(int(iters), 1) - 1.0) / k
    out["speculative_batched"] = leg(
        _device_time_ms(sfn, t_params, d_params, prompt, reps=reps),
        n=batch * new_tokens, draft_layers=2, draft_dim=draft_dim, k=k,
        draft_step=resolve_step_impl(
            draft_spec.config, batch, prompt_len + new_tokens + k + 1, None),
        acceptance_rate=round(float(min(max(acc_b, 0.0), 1.0)), 3),
        trained=True)
    # the speedup denominator is the plain batched decode of the SAME
    # trained weights (like fp_b1_trained for the b1 claim): weight-
    # independence of plain decode cost is measured, never assumed
    out["fp_trained"] = leg(_device_time_ms(fn, t_params, prompt, key,
                                            reps=reps), n=batch * new_tokens,
                            step_impl=step_b)
    out["speculative_speedup_vs_fp_batched"] = round(
        out["speculative_batched"]["tokens_per_sec"]
        / out["fp_trained"]["tokens_per_sec"], 3)

    # k=12 promoted from round-4 prose (82.0k tok/s then): a longer draft
    # window commits more tokens per target pass while trained-pair
    # acceptance stays high; recorded + tripwired like every other leg
    k12 = 12
    sfn12 = make_speculative_generate_fn(spec, draft_spec, new_tokens, k=k12,
                                         with_stats=True)
    toks, iters12 = sfn12(t_params, d_params, prompt)
    np.asarray(toks)
    acc12 = ((new_tokens - 1) / max(int(iters12), 1) - 1.0) / k12
    out["speculative_k12"] = leg(
        _device_time_ms(sfn12, t_params, d_params, prompt, reps=reps),
        n=batch * new_tokens, draft_layers=2, draft_dim=draft_dim, k=k12,
        draft_step=resolve_step_impl(
            draft_spec.config, batch, prompt_len + new_tokens + k12 + 1, None),
        acceptance_rate=round(float(min(max(acc12, 0.0), 1.0)), 3),
        trained=True)

    # b64 lockstep speculative, bf16 vs int8 KV caches: at this batch the
    # per-row KV reads are the dominant decode cost (the plain fp_b64 ->
    # kv_int8_b64 pair measured 1.91x), so halving cache traffic should
    # compound with the draft's sequential-step savings — measured, not
    # assumed, incl. the lockstep acceptance decay at 64 rows
    toks, iters64 = sfn(t_params, d_params, prompt_big)
    np.asarray(toks)
    acc64 = ((new_tokens - 1) / max(int(iters64), 1) - 1.0) / k
    out["speculative_b64"] = leg(
        _device_time_ms(sfn, t_params, d_params, prompt_big, reps=reps),
        n=big * new_tokens, draft_layers=2, draft_dim=draft_dim, k=k,
        draft_step=resolve_step_impl(
            draft_spec.config, big, prompt_len + new_tokens + k + 1, None),
        acceptance_rate=round(float(min(max(acc64, 0.0), 1.0)), 3),
        trained=True)
    qsfn = make_speculative_generate_fn(spec, draft_spec, new_tokens, k=k,
                                        with_stats=True, quantize_cache=True)
    toks, qiters64 = qsfn(t_params, d_params, prompt_big)
    np.asarray(toks)
    qacc64 = ((new_tokens - 1) / max(int(qiters64), 1) - 1.0) / k
    out["speculative_kv_int8_b64"] = leg(
        _device_time_ms(qsfn, t_params, d_params, prompt_big, reps=reps),
        n=big * new_tokens, draft_layers=2, draft_dim=draft_dim, k=k,
        kv_cache="int8",
        acceptance_rate=round(float(min(max(qacc64, 0.0), 1.0)), 3),
        trained=True)
    # one wall fallback anywhere taints the whole section's tag: a wall
    # number under a device-keyed baseline is the false-tripwire class
    # this methodology change exists to kill
    source = "device" if all(s == "device" for s in sources) else "wall"
    out["timing"] = f"{source}-median-of-{reps}"
    return out


# (seq_len, batch, model_dim, num_layers, num_heads, steps) for the LM
# train legs.  The head-dim pairs are the controlled experiment the
# round-3 verdict asked for, and it is conclusive (v5e DEVICE time,
# 2026-07-31): at IDENTICAL FLOPs, head_dim 128 (4 heads at 512-dim)
# reaches 0.577 MFU at 2k and 0.515 at 8k where head_dim 64 (8 heads)
# caps at 0.389 / 0.295.  The bound at head_dim 64 is structural, not a
# schedule problem: the attention matmuls contract over 64 — HALF the
# MXU's 128-wide systolic dimension — and carry twice the per-score
# VPU/stat overhead per matmul FLOP; a block re-sweep under the fused
# backward moved the 8k-h8 leg < 1%.  The 1024-dim/16-layer leg (head_dim
# 128, 0.689 MFU) shows the same effect at scale.  steps are sized so
# dispatch overhead stays negligible even in wall terms; timings are
# on-device regardless.
# 32k HBM watch-out: in round 2 a 6-step 32k run inside the full bench
# (after the earlier legs' HBM pressure) once degraded ~25x to 24s/step;
# the fused backward's smaller footprint made 8 steps measure sane
# (692ms/step, round-3 full-bench run), but if the 32k leg ever reports a
# wildly slow step again, suspect HBM pressure from the preceding legs
# first and drop its step count back down.
_LM_LEGS = (
    # HEADLINE rows: head_dim 128 (4 heads at 512-dim) — the recommended
    # and now-default config (models/transformer.py); the h8/head_dim-64
    # rows below stay as the controlled comparison
    (2048, 8, 512, 8, 4, 100),
    (8192, 2, 512, 8, 4, 50),
    (32768, 1, 512, 8, 4, 8),
    (2048, 4, 1024, 16, 8, 30),
    # comparison rows: head_dim 64 (the pre-round-5 default)
    (2048, 8, 512, 8, 8, 100),
    (8192, 2, 512, 8, 8, 50),
    (32768, 1, 512, 8, 8, 8),
)


def _bench_ring(l_local: int, *, batch: int = 1, heads: int = 8,
                head_dim: int = 64, steps: int = 30):
    """Ring-attention PER-BLOCK compute: flash kernel vs dense XLA on one
    [B, l_local, H, D] block, fwd+bwd — the measurement behind
    ``ring_attention``'s auto-select threshold (``ops/attention.py ::
    ring_block_impl``: flash when l_local * head_dim >= 2048 * 64 —
    the crossover tracks per-block work, not length).  Round-3 verdict
    task 4: these crossover numbers lived only in a docstring with no
    tripwire; now they are bench legs with ``vs_baseline``, so threshold
    drift after a kernel change trips visibly.

    The timed work mirrors one LIVE ring step: block attention WITH the
    logsumexp output (the ring merge needs it) and full gradients.
    Times are ON-DEVICE (``_device_time_ms``): at these ~3-10ms/step
    scales a wall reading would carry ~30-100% host-dispatch noise."""
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.ops.flash_attention import flash_attention_with_lse

    rng = np.random.default_rng(0)
    shape = (batch, l_local, heads, head_dim)
    q, k, v = (jnp.asarray(rng.normal(size=shape) * 0.1, dtype=jnp.bfloat16)
               for _ in range(3))

    def dense_with_lse(q, k, v, causal=True):
        # the dense branch of ring_attention.block_attn: f32 scores, (o, lse)
        scale = 1.0 / jnp.sqrt(jnp.float32(head_dim))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        if causal:
            pos = jnp.arange(l_local)
            logits = jnp.where((pos[:, None] >= pos[None, :])[None, None],
                               logits, -jnp.inf)
        m = jnp.max(logits, axis=-1)
        p = jnp.exp(logits - m[..., None])
        l_sum = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
        return (o / l_sum.transpose(0, 2, 1)[..., None]).astype(q.dtype), \
            m + jnp.log(l_sum)

    def loss_of(fn):
        def loss(q, k, v):
            o, lse = fn(q, k, v, causal=True)
            # both outputs live (the ring merge differentiates through lse)
            return jnp.sum(o.astype(jnp.float32)) + 1e-3 * jnp.sum(lse)

        return loss

    from distkeras_tpu.ops.attention import ring_block_impl

    flash_ms, dense_ms, timing, speedup = _ab_kernel_ms(
        loss_of(flash_attention_with_lse), loss_of(dense_with_lse),
        steps, q, k, v)
    return {
        "l_local": l_local,
        "batch": batch,
        "heads": heads,
        "head_dim": head_dim,
        "flash_ms": round(flash_ms, 3),
        "dense_ms": round(dense_ms, 3),
        "flash_speedup": speedup,
        "timing": timing,
        # what ring_attention actually auto-selects for this shard length
        # (shared predicate — restating the threshold here would hide the
        # drift this leg exists to catch)
        "auto_selects": ring_block_impl(l_local, head_dim),
    }


def _bench_feed(*, batch: int = 1024, total_batches: int = 96, reps: int = 3,
                sweep_batches_per_chunk=(4, 8, 16, 32), sweep_reps: int = 2):
    """Feed-path overlap: chunked MNIST-CNN epochs timed three ways —
    all chunks pre-placed on device (pure compute), sequential
    place-then-train (the pre-round-5 loop), and the double-buffered
    ``prefetch_to_device`` loop the trainers use.  ``feed_overhead``
    = 1 - compute/wall for each loop.

    Round-6 additions (verdict weak #4/#6): (1) a ``chunk_mb`` SWEEP —
    the same ``total_batches`` of data fed as 4/8/16/32-batch chunks
    (~12/25/49/98 MB) through the prefetch loop; the fastest size is
    promoted IN-RUN to be the config of the headline three-way
    comparison and recorded as ``best_chunk_mb`` (the measured value
    behind ``data.dataset.DEFAULT_CHUNK_BUDGET_BYTES``); (2) a per-chunk
    ``decomposition`` — IO (producing the host chunk), wire (blocking
    H2D place), and step wall vs on-device time (profiler trace) from an
    instrumented sequential pass, so where a chunk's wall goes is a
    measured split, not an inference from totals."""
    import statistics
    import tempfile
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distkeras_tpu.data.dataset import prefetch_to_device
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.cnn import mnist_cnn_spec
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.parallel.engine import scan_epoch_fn

    spec = mnist_cnn_spec()
    model = Model.init(spec, seed=0)
    opt = optax.sgd(0.01, momentum=0.9)
    epoch_fn = scan_epoch_fn(spec.apply_fn(), get_loss("categorical_crossentropy"), opt)

    rng = np.random.default_rng(0)
    data_x = rng.normal(size=(total_batches, batch, 28, 28, 1)).astype(np.float32)
    data_y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, (total_batches, batch))]

    def make_chunks(per_chunk):
        n = (total_batches // per_chunk) * per_chunk
        return [(data_x[i:i + per_chunk], data_y[i:i + per_chunk])
                for i in range(0, n, per_chunk)]

    params0 = jax.tree.map(jnp.array, model.params)
    opt_state0 = opt.init(params0)

    def run_chunks(placed_iter):
        params = jax.tree.map(jnp.array, params0)
        opt_state = jax.tree.map(jnp.array, opt_state0)
        for xs, ys in placed_iter:
            params, opt_state, losses = epoch_fn(params, opt_state, xs, ys)
            np.asarray(losses)  # the trainer's per-chunk history read

    place = lambda ch: (jnp.asarray(ch[0]), jnp.asarray(ch[1]))

    def timed(make_iter, n_reps=reps):
        walls = []
        for _ in range(n_reps):
            it = make_iter()
            t0 = time.perf_counter()
            run_chunks(it)
            walls.append(time.perf_counter() - t0)
        med = statistics.median(walls)
        spread = round((max(walls) - min(walls)) / med, 3) if med else 0.0
        return med, spread

    # -- chunk-size sweep (prefetch loop; each size recompiles the epoch
    # program once for its [per_chunk, batch, ...] shape).  A non-divisor
    # size trains only the divisible prefix of the data, so every leg's
    # samples_per_sec counts its OWN trained samples and the promotion
    # compares throughput, not wall over unequal work ----------------------
    sweep = []
    for per_chunk in sweep_batches_per_chunk:
        host_chunks = make_chunks(per_chunk)
        leg_samples = len(host_chunks) * per_chunk * batch
        run_chunks(prefetch_to_device(iter(host_chunks), place))  # compile+warm
        t_pre, sp = timed(lambda hc=host_chunks: prefetch_to_device(iter(hc), place),
                          n_reps=sweep_reps)
        sweep.append({
            "batches_per_chunk": per_chunk,
            "chunk_mb": round(host_chunks[0][0].nbytes / 2**20, 1),
            "prefetch_ms": round(t_pre * 1e3, 1),
            "samples_per_sec": round(leg_samples / t_pre, 1),
            "spread": sp,
        })
    best = max(sweep, key=lambda s: s["samples_per_sec"])
    best_per_chunk = best["batches_per_chunk"]

    # -- headline three-way comparison AT the promoted best size -----------
    host_chunks = make_chunks(best_per_chunk)
    chunks = len(host_chunks)
    samples = chunks * best_per_chunk * batch  # what these loops train on
    pre_placed = [place(ch) for ch in host_chunks]
    jax.block_until_ready(pre_placed)
    t_compute, sp_c = timed(lambda: iter(pre_placed))
    # generator places each chunk only when consumed: the old loop's
    # transfer-after-previous-chunk-completes behavior
    t_seq, sp_s = timed(lambda: (place(c) for c in host_chunks))
    t_pre, sp_p = timed(lambda: prefetch_to_device(iter(host_chunks), place))

    # -- per-chunk decomposition (instrumented sequential pass): IO is the
    # host-side chunk production (a copy here — synthetic data stands in
    # for the page-fault cost a ColumnFile feed pays), wire is the
    # BLOCKING place, step is the train call; device time comes from the
    # module events of a trace around the pass.  Blocking on the place
    # defeats overlap by design — this pass measures the parts, the timed
    # loops above measure the composition
    io_ms, wire_ms, step_ms = [], [], []
    params = jax.tree.map(jnp.array, params0)
    opt_state = jax.tree.map(jnp.array, opt_state0)
    with tempfile.TemporaryDirectory() as td:
        with jax.profiler.trace(td):
            for xs_h, ys_h in host_chunks:
                t0 = time.perf_counter()
                xs_h, ys_h = np.array(xs_h), np.array(ys_h)  # produce
                t1 = time.perf_counter()
                placed = place((xs_h, ys_h))
                jax.block_until_ready(placed)
                t2 = time.perf_counter()
                params, opt_state, losses = epoch_fn(params, opt_state, *placed)
                np.asarray(losses)
                t3 = time.perf_counter()
                io_ms.append((t1 - t0) * 1e3)
                wire_ms.append((t2 - t1) * 1e3)
                step_ms.append((t3 - t2) * 1e3)
        dev_ms = sum(_trace_jit_durs(td))
    med = statistics.median
    decomposition = {
        "io_ms_per_chunk": round(med(io_ms), 2),
        "wire_ms_per_chunk": round(med(wire_ms), 2),
        "step_wall_ms_per_chunk": round(med(step_ms), 2),
        "device_ms_per_chunk": round(dev_ms / max(chunks, 1), 2),
    }

    # NOTE: the transfer legs' bandwidth swung >2x run to run (v5e,
    # 2026-07-31) — the sequential/prefetch comparison is only meaningful
    # when their spreads are small; the spread columns exist so a reader
    # can tell.  compute_only is stable.
    return {
        "chunks": chunks,
        "chunk_mb": round(host_chunks[0][0].nbytes / 2**20, 1),
        "best_chunk_mb": best["chunk_mb"],
        "sweep": sweep,
        "timing": "wall",
        "compute_only_ms": round(t_compute * 1e3, 1),
        "sequential_ms": round(t_seq * 1e3, 1),
        "prefetch_ms": round(t_pre * 1e3, 1),
        "spread": {"compute_only": sp_c, "sequential": sp_s, "prefetch": sp_p},
        "feed_overhead_sequential": round(max(0.0, 1 - t_compute / t_seq), 4),
        "feed_overhead_prefetch": round(max(0.0, 1 - t_compute / t_pre), 4),
        "samples_per_sec_prefetch": round(samples / t_pre, 1),
        "decomposition": decomposition,
    }


def _bench_pipeline(*, pp: int = 2, num_microbatches: int = 8, batch: int = 8,
                    seq_len: int = 256, model_dim: int = 256,
                    num_heads: int = 2, num_layers: int = 4,
                    vocab: int = 8192, reps: int = 3):
    """GPipe vs 1F1B step time on a (dp=1, pp) mesh, with the analytic
    ``head_recompute_factor`` recorded next to the measurement.  Since
    round 6 the 1F1B head + CE runs inside a ``lax.cond`` taken only on
    the last rank's valid backward units, so the factor is 1.0 (same
    unembed FLOPs as GPipe); the round-5 ``jnp.where`` form paid
    ``pp * (1 + 2(pp-1)/M)`` times GPipe's and lost at every measured M.
    The leg keeps both numbers recorded so a schedule regression trips
    as a measurement, not a docstring drift."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import small_lm_spec
    from distkeras_tpu.parallel.lm import shift_targets
    from distkeras_tpu.parallel.mesh import create_nd_mesh
    from distkeras_tpu.parallel.pipeline import (head_recompute_factor,
                                                 make_pp_train_step,
                                                 pp_state_shardings,
                                                 split_block_params)

    spec = small_lm_spec(vocab_size=vocab, model_dim=model_dim,
                         num_heads=num_heads, num_layers=num_layers,
                         max_seq_len=seq_len)
    mesh = create_nd_mesh((1, pp), ("dp", "pp"))
    opt = optax.sgd(0.01)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, vocab, size=(batch, seq_len)).astype(np.int32)
    tgts = shift_targets(toks)

    out = {"pp": pp, "num_microbatches": num_microbatches, "batch": batch,
           "seq_len": seq_len, "vocab": vocab,
           "head_recompute_factor": round(
               head_recompute_factor(pp, num_microbatches), 3)}
    for schedule in ("gpipe", "1f1b"):
        model = Model.init(spec, seed=0)
        outer, blocks = split_block_params(model.params)
        psh, osh = pp_state_shardings(mesh, opt, outer, blocks)
        params = jax.device_put(
            (jax.tree.map(jnp.asarray, outer), jax.tree.map(jnp.asarray, blocks)),
            psh)
        opt_state = jax.device_put(opt.init(params), osh)
        step = make_pp_train_step(spec, opt, mesh, num_microbatches,
                                  schedule=schedule)
        dsh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("dp"))
        tok_d = jax.device_put(toks, dsh)
        tgt_d = jax.device_put(tgts, dsh)
        state = {"p": params, "o": opt_state}

        def run_once(state=state, step=step, tok_d=tok_d, tgt_d=tgt_d):
            # donated params/opt_state: thread the new state through so
            # every timed call uses live buffers
            state["p"], state["o"], loss = step(state["p"], state["o"],
                                                tok_d, tgt_d)
            return loss

        ms, spread, source = _device_time_ms(run_once, reps=reps)
        out[schedule] = {"ms_per_step": round(ms, 2),
                         "wall_spread": spread, "timing": source}
    g, f = out["gpipe"]["ms_per_step"], out["1f1b"]["ms_per_step"]
    if g:
        out["1f1b_vs_gpipe"] = round(f / g, 4)
    return out


def _bench_moe_capacity_sweep(*, model_dim: int, num_heads: int, vocab: int,
                              experts: int, batch: int, seq_len: int,
                              num_layers: int, steps: int, factors,
                              aux_weight: float = 0.01):
    """Trained-router drop rates across capacity factors (satellite of the
    sparse-dispatch issue): the recorded ``dropped_fraction`` numbers were
    UNTRAINED-router worst cases (18-30% at factor 2, BENCH_r05) — the
    load-balance aux loss exists precisely to push them toward zero, so
    this sweep trains the MoE LM (adam, ``steps`` batches of fresh random
    tokens, one compiled scan per factor) and records the drop/load stats
    at the START and END of training for each factor.  Runs on the sorted
    dispatch path at a compact depth (the routing statistics are
    per-layer; depth only multiplies identical routers)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import small_lm_spec
    from distkeras_tpu.parallel.moe import _collect_router_stats

    t = batch * seq_len
    rng = np.random.default_rng(0)
    toks = rng.integers(0, vocab, size=(steps, batch, seq_len)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=-1)  # CE below drops the last position

    results = []
    for factor in factors:
        cap = max(1, -(-int(factor * t) // experts))
        spec = small_lm_spec(vocab_size=vocab, model_dim=model_dim,
                             num_heads=num_heads, num_layers=num_layers,
                             max_seq_len=seq_len, moe_experts=experts,
                             moe_capacity=cap, moe_top_k=1,
                             moe_dispatch="sorted")
        module = spec.build()
        opt = optax.adam(3e-3)

        def loss_fn(params, tok, tgt, module=module):
            logits, variables = module.apply(
                {"params": params}, tok, mutable=["aux_loss", "router_stats"])
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), tgt.astype(jnp.int32))[:, :-1].mean()
            aux_leaves = jax.tree.leaves(variables.get("aux_loss", {}))
            aux = sum(aux_leaves) / len(aux_leaves)
            stats = {k: sum(v) / len(v) for k, v in _collect_router_stats(
                variables.get("router_stats", {})).items()}
            return ce + aux_weight * aux, stats

        @jax.jit
        def train(params, opt_state, toks_d, tgts_d, opt=opt, loss_fn=loss_fn):
            def body(carry, data):
                params, opt_state = carry
                (loss, stats), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, *data)
                updates, opt_state = opt.update(grads, opt_state, params)
                return (optax.apply_updates(params, updates), opt_state), (
                    loss, stats["dropped_fraction"], stats["max_expert_load"])

            _, ys = jax.lax.scan(body, (params, opt_state), (toks_d, tgts_d))
            return ys

        model = Model.init(spec, seed=0)
        params = jax.tree.map(jnp.asarray, model.params)
        losses, drops, loads = train(params, opt.init(params),
                                     jnp.asarray(toks), jnp.asarray(tgts))
        drops, loads = np.asarray(drops), np.asarray(loads)
        tail = max(1, min(5, steps // 4))
        results.append({
            "capacity_factor": factor,
            "capacity": cap,
            "dropped_fraction_untrained": round(float(np.mean(drops[:tail])), 4),
            "dropped_fraction_trained": round(float(np.mean(drops[-tail:])), 4),
            "max_expert_load_trained": round(float(np.mean(loads[-tail:])), 3),
            "final_loss": round(float(np.asarray(losses)[-1]), 4),
            "train_steps": steps,
        })
    return results


def _bench_moe(*, batch: int = 4, seq_len: int = 512, model_dim: int = 512,
               num_heads: int = 4, num_layers: int = 8, vocab: int = 8192,
               experts: int = 8, reps: int = 3, sweep_layers: int = 2,
               sweep_steps: int = 150,
               capacity_factors=(1.0, 1.25, 1.5, 2.0)):
    """Switch-MoE TransformerLM train step (make_moe_lm_train_step) on the
    real chip: tokens/sec + expert-FLOP-accounted MFU for top-1 (Switch)
    and top-2 (GShard-style) routing — each under BOTH dispatch impls
    (``top1``/``top2`` run the sorted gather path, ``top1_dense``/
    ``top2_dense`` the round-5 one-hot einsums, so the dispatch-tax
    removal is an A/B number, not a claim) — plus the trained-router
    capacity-factor sweep and the issue-2 acceptance tripwires.

    MFU accounting: the model-required matmul FLOPs — dense projections,
    causal attention, unembed, router, and the EXECUTED expert compute
    (E * capacity slots through up/down, i.e. the capacity-padded slabs
    the MXU actually runs, x3 for fwd+bwd) — over device time.  Dispatch/
    combine work is ROUTING OVERHEAD, excluded from MFU and reported as
    ``dispatch_flops_pct`` per impl (``parallel.moe.dispatch_matmul_flops``
    is the single source of truth: 4·T·E·C·D dense, 0 sorted).  This
    field's denominator is the whole MODEL's matmul FLOPs (attention +
    unembed included); the train step's sown stat of the same name is
    MoE-layer-local and therefore reads higher under dense dispatch —
    both are exactly 0 on the sorted path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import small_lm_spec
    from distkeras_tpu.parallel.mesh import create_nd_mesh
    from distkeras_tpu.parallel.moe import (dispatch_matmul_flops,
                                            make_moe_lm_train_step,
                                            moe_data_sharding,
                                            moe_state_shardings)
    from distkeras_tpu.parallel.lm import shift_targets

    e, f = model_dim, 4 * model_dim
    t = batch * seq_len
    cap = -(-2 * t // experts)  # the TransformerBlock default (factor-2)
    mesh = create_nd_mesh((1, 1), ("dp", "ep"))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, vocab, size=(batch, seq_len)).astype(np.int32)
    tgts = shift_targets(toks)
    peak = _peak_flops(jax.devices()[0].device_kind)

    # per-step matmul FLOPs (fwd x3 for fwd+bwd), PaLM-style: per layer
    # the experts' executed slabs (E*cap slots through up+down), qkv+proj
    # (4e^2 per token), causal attention (2*B*L^2*E fwd), the router; plus
    # the tied unembed once
    expert_fl = 3 * 4 * experts * cap * e * f
    attn_proj_fl = 3 * (2 * t * 4 * e * e + 2 * batch * seq_len * seq_len * e)
    router_fl = 3 * 2 * t * e * experts
    unembed_fl = 3 * 2 * t * e * vocab
    model_fl = num_layers * (expert_fl + attn_proj_fl + router_fl) + unembed_fl

    out = {"batch": batch, "seq_len": seq_len, "experts": experts,
           "capacity": cap}
    for top_k in (1, 2):
        for impl in ("sorted", "dense"):
            dispatch_fl = num_layers * 3 * dispatch_matmul_flops(
                t, experts, cap, e, impl)
            spec = small_lm_spec(vocab_size=vocab, model_dim=model_dim,
                                 num_heads=num_heads, num_layers=num_layers,
                                 max_seq_len=seq_len, moe_experts=experts,
                                 moe_top_k=top_k, moe_dispatch=impl)
            model = Model.init(spec, seed=0)
            opt = optax.sgd(0.01)
            step = make_moe_lm_train_step(spec, opt, mesh)
            psh, osh = moe_state_shardings(mesh, opt, model.params)
            params = jax.device_put(jax.tree.map(jnp.asarray, model.params), psh)
            opt_state = jax.device_put(opt.init(params), osh)
            dsh = moe_data_sharding(mesh)
            tok_d, tgt_d = jax.device_put(toks, dsh), jax.device_put(tgts, dsh)
            state = {"p": params, "o": opt_state, "stats": None}

            def run_once(state=state, step=step, tok_d=tok_d, tgt_d=tgt_d):
                # donated params/opt_state: thread the NEW state through so
                # every call uses live buffers
                state["p"], state["o"], loss, state["stats"] = step(
                    state["p"], state["o"], tok_d, tgt_d)
                return loss

            ms, spread, source = _device_time_ms(run_once, reps=reps)
            sec = ms / 1e3
            name = f"top{top_k}" if impl == "sorted" else f"top{top_k}_dense"
            out[name] = {
                "tokens_per_sec": round(t / sec, 1),
                "ms_per_step": round(ms, 2),
                "mfu": round(model_fl / sec / peak, 4),
                "dispatch_impl": impl,
                "dispatch_flops_pct": round(
                    100 * dispatch_fl / (model_fl + dispatch_fl), 1),
                "dropped_fraction": round(float(state["stats"]["dropped_fraction"]), 4),
                "max_expert_load": round(float(state["stats"]["max_expert_load"]), 3),
                "wall_spread": spread,
                "timing": source,
            }
    for top_k in (1, 2):
        s, d = out[f"top{top_k}"], out[f"top{top_k}_dense"]
        out[f"sorted_vs_dense_top{top_k}"] = round(
            s["tokens_per_sec"] / d["tokens_per_sec"], 4)

    try:
        out["capacity_sweep"] = _bench_moe_capacity_sweep(
            model_dim=model_dim, num_heads=num_heads, vocab=vocab,
            experts=experts, batch=batch, seq_len=seq_len,
            num_layers=sweep_layers, steps=sweep_steps,
            factors=capacity_factors)
    except Exception as ex:
        out["capacity_sweep"] = {"error": f"{type(ex).__name__}: {ex}"}

    # issue-2 acceptance tripwires, recorded as booleans so a regression
    # (or an unmet target) is a grep-able field, not a judgement call
    sweep = out["capacity_sweep"] if isinstance(out["capacity_sweep"], list) else []
    by_factor = {s["capacity_factor"]: s for s in sweep}
    trained_drop = by_factor.get(2.0, {}).get("dropped_fraction_trained")
    t1 = out["top1"]
    out["acceptance"] = {
        "mfu_target": 0.45,
        "mfu_ok": None if t1.get("mfu") is None else bool(t1["mfu"] >= 0.45),
        "dispatch_pct_target": 20.0,
        "dispatch_pct_ok": bool(t1["dispatch_flops_pct"] < 20.0),
        "trained_drop_target": 0.05,
        "trained_drop_ok": (None if trained_drop is None
                            else bool(trained_drop < 0.05)),
    }
    return out


def _bench_async(*, workers: int = 2, window: int = 8, batch: int = 256,
                 windows_per_epoch: int = 8, epochs: int = 3,
                 scaling_workers=(1, 4)):
    """Genuinely-async trainer family (runtime/async_trainer.py) on the
    real chip: AsyncADAG (Python hub, C++ hub, int8 Q-commits) and
    AsyncAEASGD wall throughput vs the sync window engine's, with the
    device-time share of the async wall so the dispatch overhead is a
    measured number, not a guess — plus a worker-scaling sweep (weak
    scaling: per-worker data held constant).  The ``native`` and ``int8``
    legs are the round-5 verdict's missing evidence: the C++ hub and the
    4x-smaller Q-commits existed with correctness tests only; these legs
    put wall/device numbers (and a tripwire) on each.

    Methodology: each trainer runs train() TWICE on the same instance —
    the first run compiles (the window program is cached per instance),
    the second is timed.  Timing is WALL by necessity (the async mode IS
    a host-driven loop; its per-window pull/commit/dispatch cost is the
    thing being measured).  ``device_share`` comes from a profiler trace
    of the timed run: sum of on-device module events across all workers
    over the wall time.  The last recorded share is 0.0038 (BENCH_r05,
    v5e 2026-07-31: 421 ms wall vs 1.61 ms device per window; cause not
    established); the leg exists to quantify exactly that."""
    import tempfile

    import jax
    import numpy as np

    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.cnn import mnist_cnn_spec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG, AsyncAEASGD
    from distkeras_tpu.trainers import ADAG

    spec = mnist_cnn_spec()
    rng = np.random.default_rng(0)

    def make_ds(w):
        n = w * batch * window * windows_per_epoch
        return n, Dataset({
            "features": rng.normal(size=(n, 28, 28, 1)).astype(np.float32),
            "label": np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=n)],
        })

    n, ds = make_ds(workers)
    samples = n * epochs

    def timed_run(trainer, ds=ds):
        trainer.train(ds, shuffle=False)  # compile + warm
        trainer.model = Model.init(spec, seed=0)
        trainer.history = []  # count only the timed run's windows
        with tempfile.TemporaryDirectory() as td:
            with jax.profiler.trace(td):
                t0 = time.perf_counter()
                trainer.train(ds, shuffle=False)
                # wall stops BEFORE the trace context exits: profiler
                # teardown (collect + gzip to disk) is not training time
                wall = time.perf_counter() - t0
            dev_ms = sum(_trace_jit_durs(td))
        return wall, dev_ms

    out = {"workers": workers, "window": window, "batch": batch,
           "epochs": epochs, "timing": "wall"}
    kwargs = dict(loss="categorical_crossentropy", batch_size=batch,
                  num_epoch=epochs, learning_rate=0.01, seed=0)

    def async_leg(name, cls, extra, w=workers, leg_ds=None, leg_samples=None):
        tr = cls(Model.init(spec, seed=0), num_workers=w,
                 communication_window=window, **dict(kwargs, **extra))
        wall, dev_ms = timed_run(tr, ds=leg_ds if leg_ds is not None else ds)
        n_windows = len(tr.history)
        out[name] = {
            "samples_per_sec": round((leg_samples or samples) / wall, 1),
            "wall_s": round(wall, 3),
            "device_share": round(dev_ms / 1e3 / wall, 4),
            "per_window_wall_ms": round(wall * 1e3 / max(n_windows, 1), 2),
            "per_window_device_ms": round(dev_ms / max(n_windows, 1), 2),
            "hub": "native" if extra.get("native_ps") else "python",
            "compress": extra.get("compress_commits"),
            "transport": extra.get("transport", "socket"),
            "pipeline": extra.get("pipeline", True),
            "num_shards": extra.get("num_shards", 1),
            "recv_batch_depth": extra.get("recv_batch_depth", 0),
            # final-loss parity evidence: pipelined pulls see the center one
            # commit earlier (self-staleness 1), so the issue-3 acceptance
            # records where every leg's trajectory LANDS, not just its speed
            "final_loss": (round(float(np.mean(tr.history[-8:])), 6)
                           if tr.history else None),
        }
        return out[name]

    def decomposition_leg(name, cls, extra):
        """Instrumented re-run of a leg (telemetry ON — its own wall clock,
        NOT comparable to the timed leg): the wall/wire/serialize/device
        split plus the hub's staleness distribution, per transport —
        issue-3's evidence that the transport tax actually moved."""
        from distkeras_tpu import observability as obs

        tr = cls(Model.init(spec, seed=0), num_workers=workers,
                 communication_window=window, **dict(kwargs, **extra))
        tr.train(ds, shuffle=False)  # compile + warm
        tr.model = Model.init(spec, seed=0)
        tr.history = []
        obs.enable()
        obs.reset()
        try:
            with tempfile.TemporaryDirectory() as td:
                with jax.profiler.trace(td):
                    t0 = time.perf_counter()
                    tr.train(ds, shuffle=False)
                    wall_ms = (time.perf_counter() - t0) * 1e3
                dev_ms = sum(_trace_jit_durs(td))
            snap = obs.snapshot()
        finally:
            obs.reset()
            obs.disable()
        hists = snap.get("histograms", {})

        def hsum(key):
            return float((hists.get(key) or {}).get("sum") or 0.0)

        n_windows = max(len(tr.history), 1)
        staleness = hists.get("ps_commit_staleness") or {}
        out[name]["decomposition"] = {
            "timing": "instrumented-wall",
            "wall_ms": round(wall_ms, 1),
            "device_ms": round(dev_ms, 1),
            # wire = time workers actually BLOCKED on the exchange after
            # overlap (pull stalls); serialize = frame pack time; the
            # remainder is dispatch + feed + Python loop
            "wire_stall_ms": round(hsum("ps.pull_stall_ms"), 1),
            "serialize_ms": round(hsum("ps.serialize_ms"), 3),
            "commit_wire_bytes": snap.get("counters", {}).get("ps.commit_bytes", 0.0),
            "per_window_wall_ms": round(wall_ms / n_windows, 2),
            "per_window_wire_stall_ms": round(hsum("ps.pull_stall_ms") / n_windows, 3),
            "staleness": {"count": staleness.get("count"),
                          "mean": staleness.get("mean"),
                          "max": staleness.get("max"),
                          "buckets": staleness.get("buckets")},
        }
        # zero-copy transport evidence (ISSUE 18): frames that crossed
        # shm rings, ring-full backpressure parks, and the hub's frames-
        # per-blocking-fill distribution — the batch tripwire's input
        counters = snap.get("counters", {})
        if counters.get("ps.shm_frames_total"):
            out[name]["decomposition"]["shm_frames_total"] = \
                counters.get("ps.shm_frames_total")
            out[name]["decomposition"]["shm_ring_full_waits"] = \
                counters.get("ps.shm_ring_full_waits", 0.0)
        depth = hists.get("ps_recv_batch_depth")
        if depth:
            out[name]["decomposition"]["recv_batch_depth"] = {
                "count": depth.get("count"), "mean": depth.get("mean"),
                "max": depth.get("max")}

    # transport/hub/compression dimensions on the SAME workload: python hub
    # pipelined sockets (baseline-continuity key), the inproc transport, the
    # serial pre-overhaul exchange (pipeline=False — the final-loss parity
    # reference), the C++ hub, int8 error-feedback commits, and AEASGD.
    # Individually fallible (the native .so may be absent on a dev box) — a
    # failed leg records its error, not the axe
    for name, cls, extra in (
            ("async_adag", AsyncADAG, {}),
            ("async_adag_inproc", AsyncADAG, {"transport": "inproc"}),
            ("async_adag_serial", AsyncADAG, {"pipeline": False}),
            ("async_adag_native", AsyncADAG, {"native_ps": True}),
            ("async_adag_int8", AsyncADAG, {"compress_commits": "int8"}),
            ("async_adag_shards4", AsyncADAG, {"num_shards": 4}),
            # zero-copy transport (ISSUE 18): frames over shm rings (same
            # bytes, no socket) and batched socket receives (recvmmsg)
            ("shm_ring", AsyncADAG, {"transport": "shm"}),
            ("recv_batch", AsyncADAG, {"recv_batch_depth": 8}),
            ("async_aeasgd", AsyncAEASGD, {"rho": 2.0})):
        try:
            async_leg(name, cls, extra)
        except Exception as ex:
            out[name] = {"error": f"{type(ex).__name__}: {ex}"}

    # per-transport decomposition (socket vs inproc vs shm vs batched),
    # on the headline config
    for name, extra in (("async_adag", {}),
                        ("async_adag_inproc", {"transport": "inproc"}),
                        ("shm_ring", {"transport": "shm"}),
                        ("recv_batch", {"recv_batch_depth": 8})):
        if isinstance(out.get(name), dict) and "error" not in out[name]:
            try:
                decomposition_leg(name, AsyncADAG, extra)
            except Exception as ex:
                out[name]["decomposition"] = {"error": f"{type(ex).__name__}: {ex}"}

    # weak-scaling points (per-worker data constant): does adding workers
    # add throughput, or does the shared hub serialize them?  The
    # `workers`-worker point is the async_adag leg above; only the other
    # counts run here
    out["scaling"] = {}
    if isinstance(out.get("async_adag"), dict) and "error" not in out["async_adag"]:
        out["scaling"][str(workers)] = {
            "samples_per_sec": out["async_adag"]["samples_per_sec"],
            "per_window_wall_ms": out["async_adag"]["per_window_wall_ms"]}
    for w in scaling_workers:
        if w == workers:
            continue
        try:
            n_w, ds_w = make_ds(w)
            leg = async_leg(f"async_adag_w{w}", AsyncADAG, {}, w=w,
                            leg_ds=ds_w, leg_samples=n_w * epochs)
            out["scaling"][str(w)] = {
                "samples_per_sec": leg["samples_per_sec"],
                "per_window_wall_ms": leg["per_window_wall_ms"]}
        except Exception as ex:
            out["scaling"][str(w)] = {"error": f"{type(ex).__name__}: {ex}"}

    # sync denominator: the SAME update family (ADAG) through the compiled
    # window engine on the same data and epoch count — one device here, so
    # this is the single-chip sync path the async mode competes with
    try:
        sync = ADAG(Model.init(spec, seed=0), num_workers=1,
                    communication_window=window, **kwargs)
        wall, dev_ms = timed_run(sync)
        out["sync_adag"] = {"samples_per_sec": round(samples / wall, 1),
                            "wall_s": round(wall, 3),
                            "device_share": round(dev_ms / 1e3 / wall, 4)}
    except Exception as ex:
        # a dead sync denominator must not axe the async legs and their
        # decomposition evidence — the ratios below just come back absent
        out["sync_adag"] = {"error": f"{type(ex).__name__}: {ex}"}

    # hub-scaling leg (ISSUE 6): pure PS-level commit throughput at 1 vs 4
    # center shards — the single-socket/single-lock ceiling measured
    # directly, without training noise.  Individually fallible like every
    # other leg
    try:
        out["shard_scaling"] = _bench_async_shards()
    except Exception as ex:
        out["shard_scaling"] = {"error": f"{type(ex).__name__}: {ex}"}

    # native feature-parity legs (ISSUE 11): each newly ported feature
    # combination on BOTH hubs, with a per-leg native-beats-python
    # tripwire.  Individually fallible like every other leg
    try:
        out["native_features"] = _bench_async_native_features()
    except Exception as ex:
        out["native_features"] = {"error": f"{type(ex).__name__}: {ex}"}

    _async_acceptance(out)
    return out


def _shard_bench_hub_proc(shapes, conn):
    """Child-process entry (spawn-safe, module level): one PS hub process
    serving one shard's slice — the ``distkeras-ps --shard-index``
    topology, so the 1-shard leg is bottlenecked by exactly what a real
    single-hub deployment is (one process's socket stack, lock and
    interpreter).  Telemetry runs locally; the final stats ride back over
    the pipe."""
    import numpy as np

    from distkeras_tpu import observability as obs
    from distkeras_tpu.runtime.parameter_server import DeltaParameterServer

    obs.enable()
    hub = DeltaParameterServer([np.zeros(s, np.float32) for s in shapes],
                               idle_timeout=None)
    hub.start()
    conn.send(hub.port)
    conn.recv()  # stop request
    hist = (obs.snapshot()["histograms"]
            .get('ps_rpc_seconds{rpc="commit"}') or {})
    conn.send({"num_updates": int(hub.num_updates),
               "hub_commit_s": hist.get("sum")})
    hub.stop()


def _shard_bench_worker_proc(addrs, shapes, num_shards, commits, max_inflight,
                             conn):
    """Child-process entry (spawn-safe, module level): one striped commit
    blaster.  Ready/go handshake over the pipe keeps process startup and
    connection warmup out of the timed window."""
    import numpy as np

    from distkeras_tpu.runtime.parameter_server import (
        ShardedPSClient, shard_plan)

    templates = [np.zeros(s, np.float32) for s in shapes]
    delta = [np.full_like(t, 1e-3) for t in templates]
    plan = shard_plan(templates, num_shards)
    client = ShardedPSClient(addrs, templates, plan, max_inflight=max_inflight)
    client.pull()  # connections + landing buffers warm
    conn.send("ready")
    conn.recv()  # go
    for _ in range(commits):
        client.commit_nowait(delta)
    client.drain()
    conn.send("done")
    client.close()


def _bench_async_shards(*, shard_counts=(1, 4), workers: int = 8,
                        leaves: int = 16, leaf_elems: int = 2048,
                        commits_per_worker: int = 300, max_inflight: int = 8):
    """Sharded-hub commit throughput (ISSUE 6 acceptance leg): ``workers``
    worker PROCESSES blast striped commits at 1 vs 4 hub shard PROCESSES
    (one Python hub per shard — the ``distkeras-ps --shard-index``
    deployment shape), and the aggregate throughput ratio is the evidence
    that partitioning the center removed the single-hub ceiling (target:
    >= 3x at 4 shards, near-linear).  Processes, not threads, on both
    sides: in-process workers share one GIL and measure the CLIENT, not
    the hub.  The payload is deliberately small (16 x 8 KiB leaves) so
    per-commit hub work — syscalls, decode, lock, ack — is the ceiling
    rather than loopback bandwidth, which one machine cannot shard.
    ``cpus`` is recorded because the figure needs ~(workers + shards)
    runnable processes to mean anything; a 2-core container reports a
    degraded ratio, the tripwire stays None-degrading, and the real
    figure comes from bench hardware."""
    import multiprocessing as mp

    from distkeras_tpu.runtime import networking as net
    from distkeras_tpu.runtime.parameter_server import shard_plan

    shapes = [(int(leaf_elems),) for _ in range(leaves)]
    center_bytes = leaves * leaf_elems * 4
    out = {"workers": workers, "leaves": leaves, "leaf_elems": leaf_elems,
           "commits_per_worker": commits_per_worker,
           "center_kb": round(center_bytes / 1024, 1),
           "hub": "python-process-per-shard",
           "cpus": os.cpu_count(),
           "shard_counts": list(shard_counts)}
    # forkserver when available: children come from a clean server process
    # (no re-exec of the caller's __main__, safe to start from a threaded
    # parent); spawn is the portable fallback.  Plain fork is never safe
    # here — the parent may hold live hub threads
    try:
        ctx = mp.get_context("forkserver")
    except ValueError:
        ctx = mp.get_context("spawn")

    def one_leg(num_shards: int) -> dict:
        import numpy as np

        templates = [np.zeros(s, np.float32) for s in shapes]
        plan = shard_plan(templates, num_shards)
        hub_pipes, hub_procs, w_pipes, w_procs = [], [], [], []
        try:
            for sid in range(num_shards):
                parent, child = ctx.Pipe()
                proc = ctx.Process(
                    target=_shard_bench_hub_proc,
                    args=([shapes[i] for i in plan.assignments[sid]], child),
                    daemon=True)
                proc.start()
                hub_pipes.append(parent)
                hub_procs.append(proc)
            addrs = []
            for pipe in hub_pipes:
                if not pipe.poll(60):
                    raise RuntimeError("hub shard process failed to report "
                                       "its port within 60s")
                addrs.append(("127.0.0.1", pipe.recv()))
            for _ in range(workers):
                parent, child = ctx.Pipe()
                proc = ctx.Process(
                    target=_shard_bench_worker_proc,
                    args=(addrs, shapes, num_shards, commits_per_worker,
                          max_inflight, child),
                    daemon=True)
                proc.start()
                w_pipes.append(parent)
                w_procs.append(proc)
            for pipe in w_pipes:
                if not pipe.poll(120):
                    raise RuntimeError("worker process failed to warm up "
                                       "within 120s")
                pipe.recv()
            t0 = time.perf_counter()
            for pipe in w_pipes:
                pipe.send("go")
            for pipe in w_pipes:
                if not pipe.poll(300):
                    raise RuntimeError("worker process did not finish its "
                                       "commits within 300s")
                pipe.recv()
            wall = time.perf_counter() - t0
            logical = workers * commits_per_worker
            stripe_bytes = sum(
                net.tensor_frame_len([templates[i] for i in idxs])
                for idxs in plan.assignments)
            per_shard = {}
            for sid, pipe in enumerate(hub_pipes):
                pipe.send("stop")
                stats = pipe.recv() if pipe.poll(30) else {}
                shard_frame = net.tensor_frame_len(
                    [templates[i] for i in plan.assignments[sid]])
                n_commits = int(stats.get("num_updates") or 0)
                hub_s = stats.get("hub_commit_s")
                per_shard[str(sid)] = {
                    "leaves": len(plan.assignments[sid]),
                    "center_kb": round(plan.shard_bytes[sid] / 1024, 1),
                    "commits": n_commits,
                    "wire_mb": round(n_commits * shard_frame / 1e6, 2),
                    "hub_commit_s": (round(float(hub_s), 4)
                                     if hub_s is not None else None),
                }
            return {
                "wall_s": round(wall, 4),
                "logical_commits": logical,
                "commits_per_sec": round(logical / wall, 2),
                "mb_per_sec": round(logical * stripe_bytes / 1e6 / wall, 2),
                "per_shard": per_shard,
            }
        finally:
            for proc in w_procs + hub_procs:
                proc.join(timeout=10)
                if proc.is_alive():
                    proc.terminate()

    for num_shards in shard_counts:
        try:
            out[str(num_shards)] = one_leg(int(num_shards))
        except Exception as ex:
            out[str(num_shards)] = {"error": f"{type(ex).__name__}: {ex}"}
    _async_shard_acceptance(out)
    return out


def _async_shard_acceptance(out: dict) -> None:
    """Attach the ISSUE-6 shard-scaling tripwire, in place: aggregate
    commit throughput at 4 shards >= 3x the 1-shard figure.  None (not a
    crash) wherever a leg is missing or errored — the PR-3 convention."""
    def _ok(name):
        return isinstance(out.get(name), dict) and "error" not in out[name]

    ratio = None
    if _ok("1") and _ok("4"):
        base = out["1"].get("commits_per_sec") or 0
        if base:
            ratio = round(out["4"]["commits_per_sec"] / base, 3)
    out["acceptance"] = {
        "shard_scaling_target": 3.0,
        "scaling_x_4_vs_1": ratio,
        "shard_scaling_ok": None if ratio is None else bool(ratio >= 3.0),
    }


def _async_acceptance(out: dict) -> None:
    """Attach the issue-3 ratios + acceptance tripwires to an async-section
    dict, in place.  Booleans (or None when a leg is missing/errored) so a
    transport regression trips visibly in the punchcard instead of hiding
    in a ratio nobody reads.  The r05 reference (BENCH_r05.json
    async_adag: per_window_wall_ms 421.15, adag_vs_sync 0.5186) is the
    pre-overhaul hot path this change exists to fix."""
    def _ok(name):
        return isinstance(out.get(name), dict) and "error" not in out[name]

    if _ok("async_adag") and _ok("sync_adag"):
        out["adag_vs_sync"] = round(out["async_adag"]["samples_per_sec"]
                                    / out["sync_adag"]["samples_per_sec"], 4)
    if _ok("async_adag_inproc") and _ok("sync_adag"):
        out["adag_inproc_vs_sync"] = round(
            out["async_adag_inproc"]["samples_per_sec"]
            / out["sync_adag"]["samples_per_sec"], 4)

    r05_wall_ms = 421.15
    speedup = (round(r05_wall_ms / out["async_adag"]["per_window_wall_ms"], 2)
               if _ok("async_adag") else None)
    parity = None
    if _ok("async_adag") and _ok("async_adag_serial"):
        fl_p = out["async_adag"]["final_loss"]
        fl_s = out["async_adag_serial"]["final_loss"]
        parity = {"pipelined": fl_p, "serial": fl_s,
                  "abs_diff": (None if fl_p is None or fl_s is None
                               else round(abs(fl_p - fl_s), 6))}
    # zero-copy transport tripwires (ISSUE 18), None-degrading like the
    # rest: the shm-ring leg must beat the inproc direct pair on
    # per-window wall (rings remove the socket from the same-host path;
    # if they cannot beat even the in-process direct transport's
    # lock-serialized exchange, the ring is overhead, not a fast path),
    # and the recv_batch leg's hub must actually have served >1 frame
    # per blocking fill (else the depth knob bought no syscalls)
    shm_vs_inproc = None
    shm_beats = None
    if _ok("shm_ring") and _ok("async_adag_inproc"):
        shm_vs_inproc = round(
            out["shm_ring"]["per_window_wall_ms"]
            / out["async_adag_inproc"]["per_window_wall_ms"], 4)
        shm_beats = bool(shm_vs_inproc <= 1.0)
    batch_ok = None
    if _ok("recv_batch"):
        depth = ((out["recv_batch"].get("decomposition") or {})
                 .get("recv_batch_depth") or {})
        if depth.get("count"):
            batch_ok = bool((depth.get("max") or 0) > 1)
    out["acceptance"] = {
        "shm_vs_inproc_per_window": shm_vs_inproc,
        "shm_beats_inproc_direct_ok": shm_beats,
        "batch_syscalls_ok": batch_ok,
        "adag_vs_sync_target": 0.85,
        "adag_vs_sync_ok": (bool(out["adag_vs_sync"] >= 0.85)
                            if "adag_vs_sync" in out else None),
        "inproc_vs_sync_target": 0.95,
        "inproc_vs_sync_ok": (bool(out["adag_inproc_vs_sync"] >= 0.95)
                              if "adag_inproc_vs_sync" in out else None),
        "r05_per_window_wall_ms": r05_wall_ms,
        "per_window_speedup_vs_r05": speedup,
        "per_window_speedup_target": 5.0,
        "per_window_speedup_ok": (None if speedup is None
                                  else bool(speedup >= 5.0)),
        "final_loss_parity": parity,
    }


def _bench_async_native_features(*, workers: int = 2, window: int = 4,
                                 batch: int = 64, windows_per_epoch: int = 4,
                                 epochs: int = 2, rows: int = 256,
                                 dim: int = 8, fields: int = 4):
    """ISSUE-11 acceptance legs: every newly ported native feature
    combination — ``sparse`` (S/V/U/X row exchange), ``adaptive`` (the
    C++ Adasum flat-combining merger) and ``sparse_adaptive`` — runs the
    SAME CTR training on the Python hub and the C++ hub, and the
    tripwire pins the native leg at-or-under the Python hub's per-window
    wall (``native_beats_python_ok``, None-degrading per the PR-3
    convention).  The pre-existing ``async_adag_native`` leg covers the
    dense plain combination; these cover what ISSUE 11 ported."""
    import numpy as np

    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.embedding import ctr_embedding_spec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG

    spec = ctr_embedding_spec(rows, dim=dim, fields=fields,
                              hidden_sizes=(16,))
    rng = np.random.default_rng(0)
    n = workers * batch * window * windows_per_epoch
    ds = Dataset({
        "features": rng.integers(0, rows, size=(n, fields)).astype(np.int32),
        "label": np.eye(2, dtype=np.float32)[rng.integers(0, 2, size=n)],
    })
    out = {"workers": workers, "window": window, "batch": batch,
           "epochs": epochs, "timing": "wall"}
    combos = {"sparse": {"sparse_tables": "auto"},
              "adaptive": {"adaptive": True},
              "sparse_adaptive": {"sparse_tables": "auto",
                                  "adaptive": True}}
    for leg, extra in combos.items():
        for hub in ("python", "native"):
            name = f"{leg}_{hub}"
            try:
                tr = AsyncADAG(Model.init(spec, seed=0), num_workers=workers,
                               communication_window=window,
                               loss="categorical_crossentropy",
                               batch_size=batch, num_epoch=epochs,
                               learning_rate=0.01, seed=0,
                               native_ps=(hub == "native"), **extra)
                tr.train(ds, shuffle=False)  # compile + warm
                tr.model = Model.init(spec, seed=0)
                tr.history = []
                t0 = time.perf_counter()
                tr.train(ds, shuffle=False)
                wall = time.perf_counter() - t0
                n_windows = max(len(tr.history), 1)
                out[name] = {
                    "hub": hub,
                    "wall_s": round(wall, 3),
                    "per_window_wall_ms": round(wall * 1e3 / n_windows, 2),
                    "samples_per_sec": round(n * epochs / wall, 1),
                }
            except Exception as ex:
                out[name] = {"error": f"{type(ex).__name__}: {ex}"}
    _native_features_acceptance(out)
    return out


def _native_features_acceptance(out: dict) -> None:
    """Attach the ISSUE-11 tripwires, in place: for each ported feature
    combination, the native leg must beat (<=) its Python-hub equivalent
    on per-window wall.  None (not a crash) wherever a leg is missing or
    errored — the PR-3 convention."""
    def _ok(name):
        return isinstance(out.get(name), dict) and "error" not in out[name]

    acc = {}
    for leg in ("sparse", "adaptive", "sparse_adaptive"):
        ratio = None
        if _ok(f"{leg}_python") and _ok(f"{leg}_native"):
            py = out[f"{leg}_python"].get("per_window_wall_ms") or 0
            nat = out[f"{leg}_native"].get("per_window_wall_ms")
            if py and nat is not None:
                ratio = round(nat / py, 4)
        acc[f"{leg}_native_vs_python"] = ratio
        acc[f"{leg}_native_beats_python_ok"] = (None if ratio is None
                                               else bool(ratio <= 1.0))
    out["acceptance"] = acc


def _bench_async_recovery(*, workers: int = 2, window: int = 8, batch: int = 256,
                          windows_per_epoch: int = 8, epochs: int = 3):
    """Issue-4 recovery leg: how the async stack behaves when its wires and
    workers actually fail.

    Three sub-legs on the same workload (AsyncADAG, the headline async
    config):

    - ``fault_free``: warm reference run — the loss/wall denominator.
    - ``sever``: an external hub behind a :class:`ChaosProxy` whose seeded
      plan severs each worker's connection once mid-run; workers reconnect
      with backoff (``max_reconnects``) and finish.  Records the
      reconnect count and the ``ps.reconnect_ms`` time-to-recover
      histogram (telemetry), plus final-loss parity vs fault-free.  Cold
      timing: a warm-up run would consume the proxy's connection ordinals
      and defuse the plan, so wall here includes compile and is NOT
      comparable to the fault-free leg — recovery time comes from the
      telemetry histogram, not the wall clock.
    - ``worker_restart``: a seeded :class:`WorkerKillPlan` kills one worker
      mid-window; the supervisor (``on_worker_failure="restart"``)
      restarts it from the hub's center.
    - ``failover`` (issue 7): an external primary with a hot standby
      (``replica_of``), killed on its commit clock mid-run by a
      :class:`HubKillPlan`; workers fail over to the standby inside the
      reconnect budget.  Records ``ps.failover_ms`` time-to-recover, the
      promoted replica's commit count vs the kill clock (the zero
      acked-commit-loss check, slack = workers x max_inflight) and
      final-loss parity vs fault-free.  Cold timing, like ``sever``.
    - ``snapshot_barrier`` (issue 7): commit throughput on a 4-shard
      in-process facade with the coordinated snapshot barrier ticking
      hard vs not at all — the <5% overhead acceptance number.

    Each sub-leg is individually fallible (error recorded, not fatal) and
    the acceptance block degrades to ``None`` for any tripwire whose
    denominator leg failed — PR 3's convention."""
    import numpy as np

    from distkeras_tpu import observability as obs
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.cnn import mnist_cnn_spec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG
    from distkeras_tpu.runtime.faults import (ChaosProxy, Fault, FaultPlan,
                                              HubKillPlan, WorkerKillPlan)
    from distkeras_tpu.runtime.launcher import start_parameter_server

    spec = mnist_cnn_spec()
    rng = np.random.default_rng(0)
    n = workers * batch * window * windows_per_epoch
    ds = Dataset({
        "features": rng.normal(size=(n, 28, 28, 1)).astype(np.float32),
        "label": np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=n)],
    })
    kwargs = dict(loss="categorical_crossentropy", batch_size=batch,
                  num_epoch=epochs, learning_rate=0.01, seed=0,
                  num_workers=workers, communication_window=window)
    out = {"workers": workers, "window": window, "batch": batch,
           "epochs": epochs}

    def final_loss(tr):
        return (round(float(np.mean(tr.history[-8:])), 6)
                if tr.history else None)

    try:
        tr = AsyncADAG(Model.init(spec, seed=0), **kwargs)
        tr.train(ds, shuffle=False)  # compile + warm
        tr.model = Model.init(spec, seed=0)
        tr.history = []
        t0 = time.perf_counter()
        tr.train(ds, shuffle=False)
        out["fault_free"] = {"wall_s": round(time.perf_counter() - t0, 3),
                             "final_loss": final_loss(tr)}
    except Exception as ex:
        out["fault_free"] = {"error": f"{type(ex).__name__}: {ex}"}

    try:
        model0 = Model.init(spec, seed=0)
        ps = start_parameter_server(model0, mode="adag", num_workers=workers,
                                    idle_timeout=120.0)
        # one sever per worker, at distinct established-pipeline frames —
        # explicit plan (not .random) so the bench exercises exactly one
        # recovery per worker every run
        plan = FaultPlan([Fault(conn=i, direction="s2c", frame=4 + 3 * i,
                                kind="sever") for i in range(workers)])
        try:
            with ChaosProxy("127.0.0.1", ps.port, plan) as proxy:
                tr2 = AsyncADAG(Model.init(spec, seed=0),
                                ps_address=("127.0.0.1", proxy.port),
                                max_reconnects=8, reconnect_backoff=0.05,
                                **kwargs)
                obs.enable()
                obs.reset()
                try:
                    t0 = time.perf_counter()
                    tr2.train(ds, shuffle=False)
                    wall = time.perf_counter() - t0
                    snap = obs.snapshot()
                finally:
                    obs.reset()
                    obs.disable()
                fired = len(proxy.faults_fired)
        finally:
            ps.stop()
        rec = (snap.get("histograms", {}).get("ps.reconnect_ms") or {})
        out["sever"] = {
            "timing": "cold-wall (includes compile; see docstring)",
            "wall_s": round(wall, 3),
            "final_loss": final_loss(tr2),
            "faults_fired": fired,
            "reconnects": snap.get("counters", {}).get("ps.reconnects", 0.0),
            "recovery_ms": {"count": rec.get("count"),
                            "mean": rec.get("mean"),
                            "max": rec.get("max")},
        }
    except Exception as ex:
        out["sever"] = {"error": f"{type(ex).__name__}: {ex}"}

    try:
        kill_plan = WorkerKillPlan([(workers - 1, windows_per_epoch // 2)],
                                   seed=4)
        tr3 = AsyncADAG(Model.init(spec, seed=0),
                        on_worker_failure="restart", max_worker_restarts=2,
                        fault_hook=kill_plan.hook, **kwargs)
        t0 = time.perf_counter()
        tr3.train(ds, shuffle=False)
        out["worker_restart"] = {
            "timing": "cold-wall",
            "wall_s": round(time.perf_counter() - t0, 3),
            "final_loss": final_loss(tr3),
            "kills_fired": len(kill_plan.fired),
            "restarts": tr3.worker_restarts,
            "worker_errors": len(tr3.worker_errors),
        }
    except Exception as ex:
        out["worker_restart"] = {"error": f"{type(ex).__name__}: {ex}"}

    try:
        model0 = Model.init(spec, seed=0)
        primary = start_parameter_server(model0, mode="adag",
                                         num_workers=workers,
                                         idle_timeout=None)
        replica = None
        # kill mid-run, on the primary's COMMIT clock (same training
        # progress every run, machine-independent)
        kill = HubKillPlan(after_commits=workers * windows_per_epoch)
        try:
            replica = start_parameter_server(
                model0, mode="adag", num_workers=workers, idle_timeout=None,
                replica_of=("127.0.0.1", primary.port))
            tr4 = AsyncADAG(Model.init(spec, seed=0),
                            ps_address=("127.0.0.1", primary.port),
                            ps_failover=("127.0.0.1", replica.port),
                            max_reconnects=8, reconnect_backoff=0.05,
                            **kwargs)
            obs.enable()
            obs.reset()
            try:
                kill.start(primary)
                t0 = time.perf_counter()
                tr4.train(ds, shuffle=False)
                wall = time.perf_counter() - t0
                snap = obs.snapshot()
            finally:
                obs.reset()
                obs.disable()
            kill.join()
            promoted = bool(replica.promoted)
            fired_at = kill.fired_at_clock
            promoted_at = replica.promoted_at_clock
            replica_commits = int(replica.num_updates)
        finally:
            kill.cancel()
            if replica is not None:
                replica.stop()
            try:
                primary.stop()
            except Exception:
                pass
        fo = (snap.get("histograms", {}).get("ps.failover_ms") or {})
        out["failover"] = {
            "timing": "cold-wall (includes compile; see docstring)",
            "wall_s": round(wall, 3),
            "final_loss": final_loss(tr4),
            "killed_at_clock": fired_at,
            # the replica's clock AT promotion: what actually replicated
            # before the switch (end-of-run num_updates would be inflated
            # by post-failover commits and prove nothing)
            "promoted_at_clock": promoted_at,
            "replica_commits": replica_commits,
            # applied-but-unacked commits at the kill instant: the honest
            # slack on the zero-ACKED-loss bound
            "acked_loss_slack": workers * tr4.max_inflight_commits,
            "promoted": promoted,
            "failovers": snap.get("counters", {}).get("ps.failovers", 0.0),
            "failover_ms": {"count": fo.get("count"), "mean": fo.get("mean"),
                            "max": fo.get("max")},
        }
    except Exception as ex:
        out["failover"] = {"error": f"{type(ex).__name__}: {ex}"}

    try:
        out["snapshot_barrier"] = _bench_snapshot_barrier()
    except Exception as ex:
        out["snapshot_barrier"] = {"error": f"{type(ex).__name__}: {ex}"}

    try:
        out["adaptive"] = _bench_async_adaptive()
    except Exception as ex:
        out["adaptive"] = {"error": f"{type(ex).__name__}: {ex}"}

    try:
        out["spot_preemption"] = _bench_async_spot_preemption()
    except Exception as ex:
        out["spot_preemption"] = {"error": f"{type(ex).__name__}: {ex}"}

    _async_recovery_acceptance(out)
    return out


def _bench_snapshot_barrier(*, shards: int = 4, min_wall_s: float = 1.0,
                            snapshot_interval: float = 0.05, reps: int = 3):
    """Commit throughput through a sharded in-process facade with
    COORDINATED snapshot sets (the commit barrier) vs INDEPENDENT
    per-shard snapshotters at the same interval — so the measured delta is
    the barrier's tax alone, not raw snapshot I/O (<5% acceptance
    target).  Each leg runs until ``min_wall_s`` has elapsed (many
    snapshot intervals per leg — a leg shorter than one interval measures
    snapshot-count luck, not cost); median of ``reps``."""
    import os as _os
    import statistics
    import tempfile

    import numpy as np

    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer, ShardedParameterServer, shard_plan)

    t = [np.zeros((128, 128), np.float32) for _ in range(2 * shards)]
    plan = shard_plan(t, shards)
    delta = [np.ones(a.shape, np.float32) for a in t]

    def one_leg(coordinated: bool) -> float:
        with tempfile.TemporaryDirectory() as d:
            if coordinated:
                def factory(w, sid):
                    return DeltaParameterServer(w, idle_timeout=None,
                                                shard_id=sid)
                ps = ShardedParameterServer(
                    t, plan, factory, snapshot_dir=d,
                    snapshot_interval=snapshot_interval)
            else:
                def factory(w, sid):
                    return DeltaParameterServer(
                        w, idle_timeout=None, shard_id=sid,
                        snapshot_dir=_os.path.join(d, f"shard-{sid:02d}"),
                        snapshot_interval=snapshot_interval)
                ps = ShardedParameterServer(t, plan, factory)
            ps.start()
            try:
                n = 0
                t0 = time.perf_counter()
                while True:
                    ps.commit_direct(delta, 0)
                    n += 1
                    elapsed = time.perf_counter() - t0
                    if elapsed >= min_wall_s:
                        return n / elapsed
            finally:
                ps.kill()

    base = statistics.median(one_leg(False) for _ in range(reps))
    coord = statistics.median(one_leg(True) for _ in range(reps))
    return {
        "shards": shards,
        "leg_wall_s": min_wall_s,
        "snapshot_interval_s": snapshot_interval,
        "per_shard_commits_per_s": round(base, 1),
        "coordinated_commits_per_s": round(coord, 1),
        "overhead_pct": round(100.0 * (base - coord) / base, 2),
    }


def _bench_async_adaptive(*, workers: int = 8, window: int = 4,
                          batch: int = 64, windows_per_epoch: int = 4,
                          epochs: int = 2,
                          jitter_s=(0.02, 0.06), seed: int = 11):
    """Issue-10 adaptive leg: at ``workers`` workers with ONE
    ChaosProxy-throttled straggler (the whole fleet fronts one proxy;
    seeded jitter applies to conn 0 only), does ``adaptive=True`` beat
    plain ADAG's final loss at comparable wall time?

    Both legs run the IDENTICAL workload, model seed, proxy seed and
    telemetry (health reports every 0.25 s, detectors on a fast drill
    cadence) — the only difference is the knob, so the delta is the
    control loop's: Adasum merging of queued commits, DynSGD-style
    per-worker scales from the live staleness series, and storm
    backpressure.  Cold timing per leg (each leg compiles its own
    trainer); the tripwire therefore compares LOSS at a bounded wall
    RATIO rather than raw walls."""
    import numpy as np

    from distkeras_tpu import observability as obs
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.observability import health as health_mod
    from distkeras_tpu.runtime.async_trainer import AsyncADAG
    from distkeras_tpu.runtime.faults import ChaosProxy
    from distkeras_tpu.runtime.launcher import start_parameter_server

    spec = ModelSpec(name="mlp",
                     config={"hidden_sizes": (32,), "num_outputs": 10},
                     input_shape=(16,))
    rng = np.random.default_rng(0)
    n = workers * batch * window * windows_per_epoch
    x = rng.normal(size=(n, 16)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=n)]
    ds = Dataset({"features": x, "label": y})
    kwargs = dict(loss="categorical_crossentropy", batch_size=batch,
                  num_epoch=epochs, learning_rate=0.05, seed=0,
                  num_workers=workers, communication_window=window)
    out = {"workers": workers, "window": window, "batch": batch,
           "epochs": epochs, "jitter_s": list(jitter_s), "seed": seed}

    for name, adaptive in (("plain", False), ("adaptive", True)):
        try:
            health_mod.reset_default()
            mon = health_mod.monitor()
            # drill cadence: the run is seconds long, the default 2 s
            # check / 10 s cooldown would let it end before reacting
            # (restored in the finally — the process monitor outlives
            # this leg)
            old_cadence = (mon.check_interval_s, mon.cooldown_s)
            mon.check_interval_s = 0.2
            mon.cooldown_s = 0.5
            model0 = Model.init(spec, seed=0)
            ps = proxy = None
            try:
                # hub and proxy start INSIDE the try: a bind failure must
                # still stop whatever came up and restore the cadence, or
                # the leak contaminates the second leg
                ps = start_parameter_server(model0, mode="adag",
                                            num_workers=workers,
                                            idle_timeout=None,
                                            adaptive=adaptive)
                proxy = ChaosProxy("127.0.0.1", ps.port,
                                   jitter_delay_s=tuple(jitter_s),
                                   seed=seed, slow_conns={0}).start()
                tr = AsyncADAG(Model.init(spec, seed=0),
                               ps_address=("127.0.0.1", proxy.port),
                               adaptive=adaptive, health_interval_s=0.25,
                               max_reconnects=8, reconnect_backoff=0.05,
                               **kwargs)
                obs.enable()
                obs.reset()
                try:
                    t0 = time.perf_counter()
                    tr.train(ds, shuffle=False)
                    wall = time.perf_counter() - t0
                    snap = obs.snapshot()
                    events = [e["kind"] for e in mon.events()]
                finally:
                    obs.reset()
                    obs.disable()
            finally:
                if proxy is not None:
                    proxy.stop()
                if ps is not None:
                    ps.stop()
                mon.check_interval_s, mon.cooldown_s = old_cadence
                health_mod.reset_default()
            counters = snap.get("counters", {})
            loss = (round(float(np.mean(tr.history[-8:])), 6)
                    if tr.history else None)
            out[name] = {
                "timing": "cold-wall (each leg compiles its own trainer)",
                "wall_s": round(wall, 3),
                "final_loss": loss,
                "merged_commits": counters.get("ps_merged_commits_total",
                                               0.0),
                "rate_scaled_commits": counters.get(
                    "ps_rate_scaled_commits_total", 0.0),
                "backpressure_hints": counters.get(
                    "ps_backpressure_hints_total", 0.0),
                "events": sorted(set(events)),
            }
        except Exception as ex:
            out[name] = {"error": f"{type(ex).__name__}: {ex}"}
    return out


def _bench_async_spot_preemption(*, workers: int = 6, preempt: int = 2,
                                 window: int = 4, batch: int = 64,
                                 windows_per_epoch: int = 6,
                                 epochs: int = 3, deadline_s: float = 5.0):
    """Issue-19 self-scaling leg: preempt ``preempt`` of ``workers``
    workers mid-run with a planned :class:`SpotPreemptionPlan` notice
    (SIGTERM-with-deadline semantics) under ``autoscale=True``.  Each
    preempted worker drains gracefully — in-flight commits acked, BYE
    sent — and the FleetController authorizes a budget-neutral respawn
    against the hub's current center, with zero operator input.

    Measures fleet throughput (windows/s from the trainer's window log)
    BEFORE the first notice vs AFTER the last one: the
    ``preemption_recovered_ok`` tripwire wants >= 90% restored.
    ``drain_zero_loss_ok`` wants every drain clean with nothing left
    unacked.  Cold timing (one compile inside the measured wall), so the
    rates — not the wall — carry the verdict."""
    import numpy as np

    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.observability import health as health_mod
    from distkeras_tpu.runtime.async_trainer import AsyncADAG
    from distkeras_tpu.runtime.faults import SpotPreemptionPlan

    spec = ModelSpec(name="mlp",
                     config={"hidden_sizes": (32,), "num_outputs": 10},
                     input_shape=(16,))
    rng = np.random.default_rng(0)
    n = workers * batch * window * windows_per_epoch
    x = rng.normal(size=(n, 16)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=n)]
    ds = Dataset({"features": x, "label": y})
    # notices land on the LAST `preempt` workers, staggered one window
    # apart, in the middle of epoch 1 — past compile, with room to
    # measure the restored rate afterwards
    mid = windows_per_epoch // 2
    plan = SpotPreemptionPlan(
        [(workers - 1 - i, mid + i) for i in range(preempt)],
        deadline_s=deadline_s)
    out = {"workers": workers, "preempt": preempt, "window": window,
           "batch": batch, "epochs": epochs, "deadline_s": deadline_s}
    health_mod.reset_default()
    mon = health_mod.monitor()
    old_cadence = (mon.check_interval_s, mon.cooldown_s)
    mon.check_interval_s = 0.2
    mon.cooldown_s = 0.5
    try:
        tr = AsyncADAG(Model.init(spec, seed=0),
                       loss="categorical_crossentropy", batch_size=batch,
                       num_epoch=epochs, learning_rate=0.05, seed=0,
                       num_workers=workers, communication_window=window,
                       elastic=True, autoscale=True,
                       health_interval_s=0.25,
                       on_worker_failure="restart", max_worker_restarts=1,
                       fault_hook=plan.hook)
        t0 = time.perf_counter()
        tr.train(ds, shuffle=False)
        wall = time.perf_counter() - t0
    finally:
        mon.check_interval_s, mon.cooldown_s = old_cadence
        health_mod.reset_default()
    log = sorted(tr._window_log)
    fired_at = sorted(plan.fired_at)
    pre_rate = post_rate = None
    if log and fired_at:
        t_start, t_end = log[0][0], log[-1][0]
        t_pre, t_post = fired_at[0], fired_at[-1]
        n_pre = sum(1 for ts, _ in log if ts < t_pre)
        n_post = sum(1 for ts, _ in log if ts >= t_post)
        if t_pre > t_start:
            pre_rate = n_pre / (t_pre - t_start)
        if t_end > t_post:
            post_rate = n_post / (t_end - t_post)
    stats = (tr.fleet_controller.stats()
             if tr.fleet_controller is not None else {})
    drains = list(tr.worker_preemptions)
    out.update({
        "timing": "cold-wall (one compile inside the measured wall)",
        "wall_s": round(wall, 3),
        "final_loss": (round(float(np.mean(tr.history[-8:])), 6)
                       if tr.history else None),
        "preemptions_fired": len(plan.fired),
        "drains": drains,
        "drains_clean": (all(d["drained_clean"] for d in drains)
                         if drains else None),
        "outstanding_after_drain": (max(d["outstanding_after_drain"]
                                        for d in drains)
                                    if drains else None),
        "respawns": stats.get("preemptions", 0),
        "pre_rate_windows_s": (round(pre_rate, 2)
                               if pre_rate is not None else None),
        "post_rate_windows_s": (round(post_rate, 2)
                                if post_rate is not None else None),
        "restarts": tr.worker_restarts,
        "worker_errors": len(tr.worker_errors),
    })
    return out


def _async_recovery_acceptance(out: dict) -> None:
    """Attach the issue-4 recovery tripwires, in place.  Booleans, or None
    when a denominator leg is missing/errored (graceful degradation,
    matching ``_async_acceptance``): recovery must COMPLETE (every planned
    fault fired, every reconnect/restart succeeded, the run finished) and
    the recovered trajectory must LAND where the fault-free one does."""
    def _ok(name):
        return isinstance(out.get(name), dict) and "error" not in out[name]

    ff_loss = out["fault_free"].get("final_loss") if _ok("fault_free") else None

    def parity(leg):
        loss = out[leg].get("final_loss") if _ok(leg) else None
        if loss is None or ff_loss is None:
            return None, None
        tol = max(0.05, 0.15 * abs(ff_loss))
        return round(abs(loss - ff_loss), 6), tol

    sever_diff, sever_tol = parity("sever")
    restart_diff, restart_tol = parity("worker_restart")
    failover_diff, failover_tol = parity("failover")
    fo = out.get("failover", {})
    barrier = out.get("snapshot_barrier", {})
    barrier_pct = (barrier.get("overhead_pct")
                   if isinstance(barrier, dict) and "error" not in barrier
                   else None)
    # issue-10 adaptive leg: adaptive vs plain ADAG with one throttled
    # straggler — loss must not be worse at comparable wall, and the
    # control loop must have visibly REACTED (merged or rate-scaled at
    # least one commit); None-degrading like every other leg
    ad = out.get("adaptive", {})

    def _leg(name):
        leg = ad.get(name) if isinstance(ad, dict) else None
        return (leg if isinstance(leg, dict) and "error" not in leg
                else None)

    ad_plain, ad_adap = _leg("plain"), _leg("adaptive")
    ad_ratio = None
    ad_beats = None
    ad_reacted = None
    if ad_plain is not None and ad_adap is not None:
        p_loss, a_loss = ad_plain.get("final_loss"), ad_adap.get("final_loss")
        p_wall, a_wall = ad_plain.get("wall_s"), ad_adap.get("wall_s")
        if p_wall:
            ad_ratio = round(a_wall / p_wall, 3)
        if p_loss is not None and a_loss is not None and ad_ratio is not None:
            # "beats at equal wall time": both legs run the same windows,
            # so equal-work walls must stay comparable (<= 1.25x) and the
            # adaptive loss must land at or below plain (small slack for
            # run-to-run float noise)
            ad_beats = bool(a_loss <= p_loss + 0.01 * max(1.0, abs(p_loss))
                            and ad_ratio <= 1.25)
    if ad_adap is not None:
        ad_reacted = bool((ad_adap.get("merged_commits") or 0)
                          + (ad_adap.get("rate_scaled_commits") or 0) >= 1)
    # issue-19 spot-preemption leg: every planned notice fired, every
    # preempted worker drained and was respawned without operator input,
    # and the fleet restored >= 90% of its pre-preemption throughput;
    # drain_zero_loss separately pins that NOTHING acked was left behind
    sp = out.get("spot_preemption", {})
    sp_ok = sp if isinstance(sp, dict) and sp and "error" not in sp else None
    sp_recovered = None
    sp_zero_loss = None
    if sp_ok is not None:
        pre = sp_ok.get("pre_rate_windows_s")
        post = sp_ok.get("post_rate_windows_s")
        planned = int(sp_ok.get("preempt") or 0)
        if pre and post is not None:
            sp_recovered = bool(
                sp_ok.get("preemptions_fired") == planned
                and (sp_ok.get("respawns") or 0) >= planned
                and post >= 0.9 * pre
                and sp_ok.get("worker_errors") == 0)
        sp_zero_loss = bool(
            len(sp_ok.get("drains") or ()) == sp_ok.get("preemptions_fired")
            and sp_ok.get("drains_clean") is True
            and sp_ok.get("outstanding_after_drain") == 0)
    out["acceptance"] = {
        "sever_recovered_ok": (bool(out["sever"]["faults_fired"] >= 1
                                    and out["sever"]["reconnects"] >= 1)
                               if _ok("sever") else None),
        "sever_loss_abs_diff": sever_diff,
        "sever_loss_tol": sever_tol,
        "sever_loss_parity_ok": (None if sever_diff is None
                                 else bool(sever_diff <= sever_tol)),
        "worker_restart_ok": (bool(out["worker_restart"]["restarts"] >= 1
                                   and out["worker_restart"]["worker_errors"] == 0)
                              if _ok("worker_restart") else None),
        "restart_loss_abs_diff": restart_diff,
        "restart_loss_tol": restart_tol,
        "restart_loss_parity_ok": (None if restart_diff is None
                                   else bool(restart_diff <= restart_tol)),
        # issue-7 failover leg: the kill fired, workers failed over, the
        # standby promoted, and every ACKED commit survived — judged at
        # PROMOTION time (clock at promotion >= kill clock minus the
        # honest in-flight slack; post-failover commits can't inflate it)
        "failover_recovered_ok": (bool(
            fo["promoted"] and fo["failovers"] >= 1
            and fo["promoted_at_clock"] is not None
            and fo["promoted_at_clock"] >= (fo["killed_at_clock"]
                                            - fo["acked_loss_slack"]))
            if _ok("failover") else None),
        "failover_ms_recorded": (bool((fo["failover_ms"]["count"] or 0) >= 1)
                                 if _ok("failover") else None),
        "failover_loss_abs_diff": failover_diff,
        "failover_loss_tol": failover_tol,
        "failover_loss_parity_ok": (None if failover_diff is None
                                    else bool(failover_diff <= failover_tol)),
        "snapshot_barrier_overhead_pct": barrier_pct,
        "snapshot_barrier_ok": (None if barrier_pct is None
                                else bool(barrier_pct < 5.0)),
        "adaptive_plain_final_loss": (ad_plain.get("final_loss")
                                      if ad_plain else None),
        "adaptive_final_loss": (ad_adap.get("final_loss")
                                if ad_adap else None),
        "adaptive_wall_ratio": ad_ratio,
        "adaptive_beats_plain_ok": ad_beats,
        "adaptive_reacted_ok": ad_reacted,
        "preemption_pre_rate_windows_s": (sp_ok.get("pre_rate_windows_s")
                                          if sp_ok else None),
        "preemption_post_rate_windows_s": (sp_ok.get("post_rate_windows_s")
                                           if sp_ok else None),
        "preemption_recovered_ok": sp_recovered,
        "drain_zero_loss_ok": sp_zero_loss,
    }


def _bench_observability(*, workers: int = 2, window: int = 8, batch: int = 256,
                         windows_per_epoch: int = 8, epochs: int = 3,
                         reps: int = 3):
    """Issue-5 observability leg: what does fleet-wide tracing COST, and
    does the attribution pipeline actually work end to end?

    Two sub-legs on the headline async config (AsyncADAG, python hub,
    pipelined sockets):

    - ``telemetry_off`` vs ``telemetry_on``: the same warmed trainer timed
      with telemetry disabled and then fully enabled (registry + spans +
      per-worker trace contexts + end-of-run trace flush to a temp
      ``DKT_TRACE_DIR``).  ``overhead_pct`` is the median-of-``reps``
      relative wall cost — the <3% acceptance target.  No profiler here:
      the leg measures telemetry's own tax, nothing else's.
    - the on-leg's flushed trace is merged (``merge_traces``) and
      ``fleet_report`` runs over it: the leg records hub-commit context
      coverage (the >=95% acceptance criterion) and whether a straggler
      ranking came back.
    """
    import os as _os
    import tempfile

    import numpy as np

    from distkeras_tpu import observability as obs
    from distkeras_tpu.observability import distributed as dtrace
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.cnn import mnist_cnn_spec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG

    spec = mnist_cnn_spec()
    rng = np.random.default_rng(0)
    n = workers * batch * window * windows_per_epoch
    ds = Dataset({
        "features": rng.normal(size=(n, 28, 28, 1)).astype(np.float32),
        "label": np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=n)],
    })
    kwargs = dict(loss="categorical_crossentropy", batch_size=batch,
                  num_epoch=epochs, learning_rate=0.01, seed=0,
                  num_workers=workers, communication_window=window)

    tr = AsyncADAG(Model.init(spec, seed=0), **kwargs)
    tr.train(ds, shuffle=False)  # compile + warm

    def timed(telemetry: bool, trace_dir=None):
        walls = []
        for _ in range(reps):
            tr.model = Model.init(spec, seed=0)
            tr.history = []
            if telemetry:
                obs.enable()
                obs.reset()
                # one rep = one job's evidence: earlier reps' flushed
                # files must not stack up as phantom extra "processes"
                # in the merged trace
                if trace_dir is not None:
                    import glob as _glob

                    for f in _glob.glob(_os.path.join(trace_dir,
                                                      "trace-*.jsonl")):
                        _os.remove(f)
            else:
                # the off leg must actually be OFF even when the operator
                # exported DKT_TELEMETRY=1 (the documented enable path) —
                # otherwise overhead_pct compares on vs on and reads ~0
                obs.disable()
            t0 = time.perf_counter()
            tr.train(ds, shuffle=False)
            walls.append(time.perf_counter() - t0)
            if telemetry:
                obs.disable()
        return float(np.median(walls))

    was_enabled = obs.enabled()
    out = {"workers": workers, "window": window, "batch": batch,
           "epochs": epochs, "reps": reps, "timing": "wall-median"}
    wall_off = timed(False)
    out["telemetry_off"] = {"wall_s": round(wall_off, 3)}

    with tempfile.TemporaryDirectory() as td:
        old_dir = _os.environ.get("DKT_TRACE_DIR")
        _os.environ["DKT_TRACE_DIR"] = td
        try:
            wall_on = timed(True, trace_dir=td)
        finally:
            if old_dir is None:
                _os.environ.pop("DKT_TRACE_DIR", None)
            else:
                _os.environ["DKT_TRACE_DIR"] = old_dir
            if was_enabled:
                obs.enable()
        merged = dtrace.merge_traces(td)
        report = dtrace.fleet_report(trace_dir=td)
    out["telemetry_on"] = {"wall_s": round(wall_on, 3)}
    out["overhead_pct"] = round((wall_on / wall_off - 1.0) * 100.0, 2)
    out["merged_trace"] = {
        "processes": merged["otherData"]["processes"],
        "spans": merged["otherData"]["spans"],
        "alignment_error_us": merged["otherData"]["alignment_error_us"],
    }
    out["fleet"] = {
        "commit_context_coverage": report["commit_context_coverage"],
        "total_commits": report["total_commits"],
        "top_straggler": report["top_straggler"],
        "workers_seen": len(report["workers"]),
    }
    _observability_acceptance(out)
    return out


def _observability_acceptance(out: dict) -> None:
    """Attach the issue-5 tripwires, in place: tracing overhead under the
    3% target, and >=95% of hub commit spans carrying a worker trace
    context.  Booleans, or None when a leg is missing/errored (graceful
    degradation, the PR-3 convention)."""
    overhead = out.get("overhead_pct")
    coverage = (out.get("fleet") or {}).get("commit_context_coverage")
    out["acceptance"] = {
        "overhead_pct": overhead,
        "overhead_pct_target": 3.0,
        "overhead_ok": None if overhead is None else bool(overhead < 3.0),
        "commit_context_coverage": coverage,
        "coverage_target": 0.95,
        "coverage_ok": None if coverage is None else bool(coverage >= 0.95),
        "straggler_ranked": (bool((out.get("fleet") or {}).get("top_straggler")
                                  is not None)
                             if isinstance(out.get("fleet"), dict) else None),
    }


def _bench_health(*, workers: int = 2, window: int = 8, batch: int = 256,
                  windows_per_epoch: int = 8, epochs: int = 3,
                  reps: int = 3, health_interval_s: float = 0.25):
    """Issue-8 fleet-health leg: what does the LIVE health plane COST with
    everything on, and does it actually see the fleet?

    Same warmed AsyncADAG / python-hub / pipelined-socket config as
    ``_bench_observability``, timed twice:

    - ``health_off``: telemetry disabled, no tracking, no reports — the
      zero-cost-when-off contract's reference wall.
    - ``health_on``: registry + spans enabled, the trainer's window
      instruments opted into sliding-window time series (``obs.track``),
      workers streaming periodic reports to the hub (wire action ``M``)
      where the rolling detectors run — the WHOLE plane.

    ``overhead_pct`` is the median-of-``reps`` relative wall cost — the
    <3% acceptance tripwire.  The on-leg also records what the plane saw:
    per-worker collector coverage, reports ingested, tracked series, and
    any ringed events (a healthy 2-worker run should fire none)."""
    import numpy as np

    from distkeras_tpu import observability as obs
    from distkeras_tpu.observability import health as _health
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.cnn import mnist_cnn_spec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG

    spec = mnist_cnn_spec()
    rng = np.random.default_rng(0)
    n = workers * batch * window * windows_per_epoch
    ds = Dataset({
        "features": rng.normal(size=(n, 28, 28, 1)).astype(np.float32),
        "label": np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=n)],
    })
    tr = AsyncADAG(Model.init(spec, seed=0),
                   loss="categorical_crossentropy", batch_size=batch,
                   num_epoch=epochs, learning_rate=0.01, seed=0,
                   num_workers=workers, communication_window=window)
    tr.train(ds, shuffle=False)  # compile + warm

    tracked = ("async_window_wall_seconds", "async_windows_total",
               "ps_commit_staleness")

    def timed(on: bool):
        walls = []
        for _ in range(reps):
            tr.model = Model.init(spec, seed=0)
            tr.history = []
            if on:
                obs.enable()
                obs.reset()
                _health.reset_default()
                for name in tracked:
                    obs.track(name)
                tr.health_interval_s = float(health_interval_s)
            else:
                # fully off even under an exported DKT_TELEMETRY=1 —
                # otherwise overhead_pct compares on vs on and reads ~0
                obs.disable()
                tr.health_interval_s = None
            t0 = time.perf_counter()
            tr.train(ds, shuffle=False)
            walls.append(time.perf_counter() - t0)
            if on:
                obs.disable()
        return float(np.median(walls))

    was_enabled = obs.enabled()
    out = {"workers": workers, "window": window, "batch": batch,
           "epochs": epochs, "reps": reps,
           "health_interval_s": health_interval_s, "timing": "wall-median"}
    try:
        wall_off = timed(False)
        out["health_off"] = {"wall_s": round(wall_off, 3)}
        wall_on = timed(True)
        out["health_on"] = {"wall_s": round(wall_on, 3)}
        out["overhead_pct"] = round((wall_on / wall_off - 1.0) * 100.0, 2)
        # evidence from the LAST on-rep (reset_default ran per rep, so
        # this is one run's view, not reps stacked)
        fleet = _health.collector().snapshot()
        seen = fleet.get("workers") or {}
        out["collector"] = {
            "workers_seen": len(seen),
            "reports_ingested": sum((e.get("meta") or {}).get("reports", 0)
                                    for e in seen.values()),
            "tracked_series": len(obs.tracked_snapshot()),
            "events": len(_health.monitor().events()),
        }
    finally:
        for name in tracked:
            obs.untrack(name)
        _health.reset_default()
        if was_enabled:
            obs.enable()
    _health_acceptance(out)
    return out


def _health_acceptance(out: dict) -> None:
    """Attach the issue-8 tripwires, in place: the fully-on health plane
    (tracking + streaming collector + detectors) under the 3% wall
    overhead target, and the collector actually covering the fleet (every
    worker reported at least once).  Booleans, or None when a leg is
    missing/errored (graceful degradation, the PR-3 convention)."""
    overhead = out.get("overhead_pct")
    col = out.get("collector") if isinstance(out.get("collector"), dict) else {}
    seen = col.get("workers_seen")
    reports = col.get("reports_ingested")
    workers = out.get("workers")
    out["acceptance"] = {
        "overhead_pct": overhead,
        "overhead_pct_target": 3.0,
        "overhead_ok": None if overhead is None else bool(overhead < 3.0),
        "workers_seen": seen,
        "fleet_covered": (None if seen is None or workers is None
                          else bool(seen >= workers)),
        "reports_ok": None if reports is None else bool(reports > 0),
    }


def _bench_embedding(*, rows: int = 25600, dim: int = 128, fields: int = 2,
                     batch: int = 32, window: int = 4,
                     windows_per_epoch: int = 4, epochs: int = 2,
                     workers: int = 2, reps: int = 3):
    """Issue-9 row-sparse embedding leg: what does the PS wire COST when a
    CTR-shaped model (one [rows, dim] table dwarfing the dense head) moves
    only the rows each window touches?

    Same AsyncADAG / python-hub / pipelined-socket config as the other
    async legs, run twice on a synthetic CTR log whose per-window batches
    draw ``batch * window * fields`` uniform ids (~1% of the vocabulary at
    the default shape):

    - ``dense``: sparse_tables=None — every window moves the whole leaf
      both ways (today's wire).
    - ``sparse``: sparse_tables="auto" — pulls carry row-id sets (action
      S/V), commits carry (row_ids, row_grads) pairs (action U).

    ``wire_bytes`` is the hub's pull+commit byte counters; the EXCHANGE
    bytes subtract each worker's one initial full-center pull (both legs
    pay it identically — it seeds the sparse caches), so the tripwire
    ratio compares the steady-state window exchange the issue is about.
    Records rows/s (committed rows over the run wall), the measured
    touched-row fraction, and the issue-9 acceptance tripwire:
    sparse exchange bytes <= 1.1 x touched_fraction x dense exchange."""
    import numpy as np

    from distkeras_tpu import observability as obs
    from distkeras_tpu.data.ctr import synthetic_ctr_dataset
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.embedding import ctr_embedding_spec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG
    from distkeras_tpu.utils import flatten_weights

    # a small head (hidden 8): the leg measures the TABLE's wire story,
    # and the head rides every sparse frame whole — at CTR shapes the
    # table dwarfs it, which is the regime the tripwire bound assumes
    spec = ctr_embedding_spec(rows, dim=dim, fields=fields,
                              hidden_sizes=(8,))
    n = workers * batch * window * windows_per_epoch
    # hot_prob=0: uniform id draws, so the touched fraction is set by
    # batch*window*fields vs rows (the 1%-fraction shape the tripwire
    # is phrased at), not by hot-set luck
    ds = synthetic_ctr_dataset(n, rows, fields=fields, seed=0, hot_prob=0.0)
    n_windows = workers * windows_per_epoch * epochs
    flat, _ = flatten_weights(Model.init(spec, seed=0).params)
    center_bytes = sum(np.asarray(w).nbytes for w in flat)

    def leg(sparse: bool):
        tr = AsyncADAG(Model.init(spec, seed=0),
                       loss="categorical_crossentropy", batch_size=batch,
                       num_epoch=epochs, learning_rate=0.05, seed=0,
                       num_workers=workers, communication_window=window,
                       sparse_tables="auto" if sparse else None)
        tr.train(ds, shuffle=False)  # compile + warm (telemetry off)
        walls = []
        counters = {}
        for _ in range(reps):
            tr.model = Model.init(spec, seed=0)
            tr.history = []
            obs.enable()
            obs.reset()
            t0 = time.perf_counter()
            tr.train(ds, shuffle=False)
            walls.append(time.perf_counter() - t0)
            counters = dict(obs.snapshot()["counters"])
            obs.disable()
            obs.reset()
        wall = float(np.median(walls))
        wire = (counters.get("ps_pull_bytes_total", 0.0)
                + counters.get("ps_commit_bytes_total", 0.0))
        exchange = max(0.0, wire - workers * center_bytes)
        out = {"wall_s": round(wall, 3), "wire_bytes": round(wire),
               "exchange_bytes": round(exchange)}
        if sparse:
            committed = counters.get("ps.sparse_rows_committed", 0.0)
            out["rows_pulled"] = round(
                counters.get("ps.sparse_rows_pulled", 0.0))
            out["rows_committed"] = round(committed)
            out["rows_per_s"] = (round(committed / wall, 1) if wall > 0
                                 else None)
            out["wire_bytes_saved"] = round(
                counters.get("ps.sparse_wire_bytes_saved", 0.0))
            out["touched_row_fraction"] = (
                round(committed / (n_windows * rows), 5)
                if n_windows * rows else None)
        return out

    def hot_leg(hot_fraction=0.01, hot_prob=0.9):
        """The issue-15 cold-start + skewed-access leg: a hot/cold CTR
        draw, a hot-tier client cache sized to ~2x the hot set, and a
        sparse-capable standby attached to the hub — so the leg measures
        the THREE hyperscale edges at once: client cache memory (bounded
        LRU vs full table), replication bytes (REPL_SPARSE row deltas vs
        the dense-R equivalent) and the cache hit economics (cold start
        misses, warm hits at skew)."""
        from distkeras_tpu.models.base import sparse_leaf_indices
        from distkeras_tpu.runtime.parameter_server import (
            ADAGParameterServer)

        ds_hot = synthetic_ctr_dataset(n, rows, fields=fields, seed=0,
                                       hot_fraction=hot_fraction,
                                       hot_prob=hot_prob)
        hot_rows = max(1, int(round(rows * hot_fraction)))
        cache_rows = min(rows, 2 * hot_rows)
        model = Model.init(spec, seed=0)
        flat_w = [np.asarray(w, np.float32)
                  for w in flatten_weights(model.params)[0]]
        sparse_idx = sparse_leaf_indices(spec, model.params)
        hub = ADAGParameterServer(flat_w, num_workers=workers,
                                  idle_timeout=None,
                                  sparse_leaves=sparse_idx)
        # bench runs are short: decay (and publish) the hot-set estimate
        # every few folds so the leg records a non-None estimate
        hub.TOUCH_DECAY_EVERY = 8
        hub.start()
        standby = ADAGParameterServer(flat_w, num_workers=workers,
                                      idle_timeout=None,
                                      sparse_leaves=sparse_idx,
                                      replica_of=("127.0.0.1", hub.port))
        standby.start()
        try:
            if not standby.wait_synced(30):
                raise RuntimeError("hot leg: standby never synced")
            tr = AsyncADAG(model, loss="categorical_crossentropy",
                           batch_size=batch, num_epoch=epochs,
                           learning_rate=0.05, seed=0,
                           num_workers=workers,
                           communication_window=window,
                           sparse_tables="auto",
                           sparse_cache_rows=cache_rows,
                           ps_address=("127.0.0.1", hub.port))
            obs.enable()
            obs.reset()
            t0 = time.perf_counter()
            tr.train(ds_hot, shuffle=False)
            wall = time.perf_counter() - t0
            counters = dict(obs.snapshot()["counters"])
            gauges = dict(obs.snapshot()["gauges"])
            obs.disable()
            obs.reset()
            repl_bytes = hub._feed.repl_sparse_bytes if hub._feed else 0
            saved = sum(v for k, v in counters.items()
                        if k.startswith("ps.repl_sparse_bytes_saved"))
            hits = sum(v for k, v in counters.items()
                       if k.startswith("ps_sparse_cache_hits_total"))
            misses = sum(v for k, v in counters.items()
                         if k.startswith("ps_sparse_cache_misses_total"))
            committed = sum(v for k, v in counters.items()
                            if k.startswith("ps.sparse_rows_committed"))
            hot_est = [v for k, v in gauges.items()
                       if k.startswith("ps.sparse_hot_rows")]
            commits = counters.get("ps_commits_total", 0.0)
            table_bytes = rows * dim * 4
            return {
                "wall_s": round(wall, 3),
                "hot_fraction": hot_fraction, "hot_prob": hot_prob,
                "cache_rows": cache_rows,
                # per-worker host bytes the hot tier holds vs the full
                # table cache a PR-9 client would hold
                "cache_bytes": cache_rows * dim * 4,
                "full_cache_bytes": table_bytes,
                "cache_memory_ratio": round(cache_rows / rows, 5),
                "cache_hits": round(hits), "cache_misses": round(misses),
                "cache_hit_rate": (round(hits / (hits + misses), 4)
                                   if hits + misses else None),
                "repl_sparse_bytes": round(repl_bytes),
                "repl_bytes_saved": round(saved),
                "repl_dense_equiv_bytes": round(repl_bytes + saved),
                "rows_committed": round(committed),
                "hot_rows_estimate": (round(max(hot_est))
                                      if hot_est else None),
                "touched_row_fraction": (
                    round(committed / (commits * rows), 5)
                    if commits and rows else None),
            }
        finally:
            standby.stop()
            hub.stop()

    was_enabled = obs.enabled()
    out = {"rows": rows, "dim": dim, "fields": fields, "batch": batch,
           "window": window, "epochs": epochs, "workers": workers,
           "reps": reps, "timing": "wall-median",
           "table_mb": round(rows * dim * 4 / 2**20, 2),
           "center_bytes": center_bytes}
    try:
        out["dense"] = leg(False)
        out["sparse"] = leg(True)
        try:
            out["hot"] = hot_leg()
        except Exception as e:  # the hot leg must not axe the PR-9 legs
            out["hot"] = {"error": f"{type(e).__name__}: {e}"}
    finally:
        if was_enabled:
            obs.enable()
        else:
            obs.disable()
    _embedding_acceptance(out)
    return out


def _embedding_acceptance(out: dict) -> None:
    """Attach the issue-9 + issue-15 tripwires, in place: the sparse
    leg's steady-state exchange bytes under ``1.1 x touched_fraction``
    of the dense leg's, with a rows/s figure recorded; the hot leg's
    replication bytes under ``1.1 x touched_fraction`` of the dense-R
    equivalent, its client cache memory scaling with the hot fraction
    (cache/table ratio <= 4x the hot fraction by construction of the
    2x-hot-set sizing, asserted anyway against drift), and a warm hit
    rate that shows the hot tier actually absorbing the skew.  Booleans,
    or None when a leg is missing/errored (graceful degradation, the
    PR-3 convention)."""
    dense = out.get("dense") if isinstance(out.get("dense"), dict) else {}
    sparse = out.get("sparse") if isinstance(out.get("sparse"), dict) else {}
    hot = out.get("hot") if isinstance(out.get("hot"), dict) else {}
    dense_bytes = dense.get("exchange_bytes")
    sparse_bytes = sparse.get("exchange_bytes")
    frac = sparse.get("touched_row_fraction")
    ratio = (round(sparse_bytes / dense_bytes, 5)
             if sparse_bytes and dense_bytes else None)
    bound = round(1.1 * frac, 5) if frac else None
    rows_per_s = sparse.get("rows_per_s")
    repl = hot.get("repl_sparse_bytes")
    repl_equiv = hot.get("repl_dense_equiv_bytes")
    hot_frac = hot.get("touched_row_fraction")
    repl_ratio = (round(repl / repl_equiv, 5)
                  if repl and repl_equiv else None)
    repl_bound = round(1.1 * hot_frac, 5) if hot_frac else None
    cache_ratio = hot.get("cache_memory_ratio")
    hot_fraction = hot.get("hot_fraction")
    hit_rate = hot.get("cache_hit_rate")
    out["acceptance"] = {
        "wire_ratio": ratio,
        "wire_ratio_bound": bound,
        "touched_row_fraction": frac,
        "sparse_wire_ok": (None if ratio is None or bound is None
                           else bool(ratio <= bound)),
        "rows_per_s": rows_per_s,
        "rows_per_s_recorded": (None if rows_per_s is None
                                else bool(rows_per_s > 0)),
        # -- issue-15 hyperscale tripwires --------------------------------
        "repl_ratio": repl_ratio,
        "repl_ratio_bound": repl_bound,
        "repl_sparse_ok": (None if repl_ratio is None or repl_bound is None
                           else bool(repl_ratio <= repl_bound)),
        "cache_memory_ratio": cache_ratio,
        "cache_memory_ok": (None if cache_ratio is None
                            or not hot_fraction
                            else bool(cache_ratio <= 4.0 * hot_fraction)),
        "cache_hit_rate": hit_rate,
        "cache_hit_ok": (None if hit_rate is None
                         else bool(hit_rate >= 0.3)),
    }


def _leg_ratio(current: float, base: float):
    """current/base rounded, or None when either side is missing/zero."""
    if not current or not base:
        return None
    return round(current / base, 4)


def _apply_leg_baselines(out: dict, baseline: dict) -> None:
    """Attach per-leg ``vs_baseline`` ratios (throughput ratios, > 1 means
    faster than the recorded best) so an MFU/decode regression trips
    visibly.  Legs are matched by config key; a methodology or config
    change simply finds no match and reports no ratio."""
    for leg in out.get("lm", ()):
        if leg.get("timing") != "device":
            continue  # wall fallback (or an untagged leg from an older
            #           build) must not ratio against device records
        key = (f"lm:{leg.get('seq_len')}x{leg.get('batch')}"
               f":d{leg.get('model_dim', 512)}h{leg.get('num_heads', 8)}")
        base = baseline.get("legs", {}).get(key, {})
        r = _leg_ratio(leg.get("tokens_per_sec"), base.get("tokens_per_sec"))
        if r is not None:
            leg["vs_baseline"] = r
    for leg in out.get("attn", ()):
        if leg.get("timing") != "device":
            continue  # wall fallback must not ratio against device records
        # ":device" in the key so a stale wall-era record (or a checkout
        # whose json predates the methodology switch) can never match
        key = f"attn:{leg.get('seq_len')}:device"
        base = baseline.get("legs", {}).get(key, {})
        # ms ratio inverted so > 1 still means "faster than baseline"
        r = _leg_ratio(base.get("flash_ms"), leg.get("flash_ms"))
        if r is not None:
            leg["vs_baseline"] = r
    for leg in out.get("ring", ()):
        if leg.get("timing") != "device":
            continue  # wall fallback must not ratio against device records
        key = (f"ring:{leg.get('l_local')}:b{leg.get('batch', 1)}"
               f"h{leg.get('heads', 8)}d{leg.get('head_dim', 64)}:device")
        base = baseline.get("legs", {}).get(key, {})
        r = _leg_ratio(base.get("flash_ms"), leg.get("flash_ms"))
        if r is not None:
            leg["vs_baseline"] = r
    moe = out.get("moe", {})
    # the bare top1/top2 keys carry the DEFAULT dispatch path (sorted as
    # of round 6; dense before) — so the first sorted capture ratios
    # against the round-5 dense record and SHOWS the dispatch-tax removal
    # as vs_baseline > 1, after which the record advances.  The *_dense
    # legs get their own keys so the A/B baseline persists independently
    for mode in ("top1", "top2", "top1_dense", "top2_dense"):
        sub = moe.get(mode)
        if isinstance(sub, dict) and sub.get("timing") == "device":
            key = (f"moe:{mode}:b{moe.get('batch')}s{moe.get('seq_len')}"
                   f"e{moe.get('experts')}:device")
            base = baseline.get("legs", {}).get(key, {})
            r = _leg_ratio(sub.get("tokens_per_sec"), base.get("tokens_per_sec"))
            if r is not None:
                sub["vs_baseline"] = r
    # async legs are wall-timed by nature (a host-driven loop IS the thing
    # measured), and their wall swung ±30% run to run (v5e, 2026-07-31) —
    # so their tripwire keys on per-window DEVICE time, which repeats; ms
    # ratio inverted
    # so > 1 still means faster
    asy = out.get("async", {})
    for mode in ("async_adag", "async_aeasgd", "async_adag_native",
                 "async_adag_int8", "async_adag_inproc", "async_adag_serial"):
        sub = asy.get(mode)
        if isinstance(sub, dict):
            key = (f"async:{mode}:w{asy.get('workers')}x{asy.get('window')}"
                   f"b{asy.get('batch')}:device-window")
            base = baseline.get("legs", {}).get(key, {})
            r = _leg_ratio(base.get("per_window_device_ms"),
                           sub.get("per_window_device_ms"))
            if r is not None:
                sub["vs_baseline"] = r
    dec = out.get("decode", {})
    # modes that run the SECTION batch (their tokens/sec scales ~linearly
    # with it, and lockstep acceptance shrinks as agreement^batch) carry
    # the batch in their key; the *_b1 modes always run batch 1 and must
    # NOT be invalidated by a section-batch change
    batched_modes = {"fp", "int8", "fp_trained", "speculative_batched",
                     "speculative_k12"}
    # fp_b64 / kv_int8_b64 / speculative_*b64 run a FIXED batch 64 (the
    # mode name carries it), independent of the section batch
    for mode in ("fp", "int8", "fp_b1", "fp_b1_trained", "fp_trained",
                 "speculative_b1", "speculative_batched", "speculative_k12",
                 "fp_b64", "kv_int8_b64", "speculative_b64",
                 "speculative_kv_int8_b64", "fp_b64_gqa", "kv_int8_b64_gqa"):
        sub = dec.get(mode)
        # methodology-coded key: generation length and timing stat are part
        # of the identity, so the round-3 min-of-2-wall/256-token records
        # can never produce a ratio against a device-median/512-token run
        bpart = f":b{dec.get('batch')}" if mode in batched_modes else ""
        key = f"decode:{mode}{bpart}:n{dec.get('new_tokens')}:{dec.get('timing')}"
        base = baseline.get("legs", {}).get(key, {})
        if isinstance(sub, dict):
            r = _leg_ratio(sub.get("tokens_per_sec"), base.get("tokens_per_sec"))
            if r is not None:
                sub["vs_baseline"] = r


def _has_error(node) -> bool:
    """True when the result carries an ``error`` anywhere: the fatal note
    at the top (which also covers "not on the chip" — ``_init_backend``
    raises) or any leg's own ``{"error": ...}``."""
    if isinstance(node, dict):
        return "error" in node or any(_has_error(v) for v in node.values())
    if isinstance(node, list):
        return any(_has_error(v) for v in node)
    return False


def main() -> None:
    out = {
        "metric": "mnist_cnn_train_samples_per_sec_per_chip",
        "value": 0.0,
        "unit": "samples/sec/chip",
        "vs_baseline": 0.0,
    }
    try:
        from distkeras_tpu.platform import enable_compile_cache

        out["compile_cache"] = enable_compile_cache()
        platform = _init_backend()
        out["platform"] = platform

        sps_per_chip, method = _bench_mnist_cnn(compute_dtype=_MNIST_DTYPE)
        out["value"] = round(sps_per_chip, 1)
        out["batch_size"] = _MNIST_BATCH
        out["compute_dtype"] = _MNIST_DTYPE
        out["methodology"] = method
        try:
            # A/B: the same headline model in plain float32 — the
            # pre-round-5 headline config — recorded next to the bf16
            # headline so the compute_dtype policy's win at this scale
            # stays a recorded number, not folklore (see _MNIST_DTYPE)
            f32_sps, f32_method = _bench_mnist_cnn()
            out["mnist_cnn_f32"] = {
                "samples_per_sec_per_chip": round(f32_sps, 1),
                "headline_vs_f32": round(sps_per_chip / f32_sps, 4),
                "methodology": f32_method,
            }
        except Exception as e:
            out["mnist_cnn_f32"] = {"error": f"{type(e).__name__}: {e}"}

        baseline_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "bench_baseline.json")
        baseline = {}
        if os.path.exists(baseline_path):
            with open(baseline_path) as f:
                baseline = json.load(f)
        vs = 1.0
        base = baseline.get("value")
        base_method = baseline.get("methodology")
        if base and baseline.get("platform", "tpu") != platform:
            # CPU-fallback throughput vs a TPU baseline is meaningless;
            # skip the ratio (keep 1.0) and flag why
            out["vs_baseline_note"] = (
                f"baseline recorded on {baseline.get('platform', 'tpu')}; "
                f"this run on {platform} — ratio not computed")
        elif base and base_method != method:
            # a ratio across bench-methodology changes measures the
            # measurement, not the chip (the round-2 dispatch-overhead
            # fix alone moved the same model 539k -> 934k; the v3 device
            # tag keeps a CPU wall fallback from ratioing against it)
            out["vs_baseline_note"] = (
                f"baseline methodology {base_method!r} != {method!r}"
                " — ratio not computed")
        elif base:
            vs = sps_per_chip / base
        out["vs_baseline"] = round(vs, 6)

        import gc

        # secondary benches are individually fallible — a failure is
        # recorded as that leg's {"error": ...} and the remaining legs
        # still run; the exit code below reports it.
        # gc between legs drops dead device buffers promptly: HBM
        # pressure from earlier legs once blew the 32k LM leg up 25x
        gc.collect()
        lm, attn, ring = [], [], []
        for seq, batch, model_dim, num_layers, num_heads, steps in _LM_LEGS:
            try:
                leg = _bench_lm(seq, batch, model_dim=model_dim,
                                num_heads=num_heads, num_layers=num_layers,
                                steps=steps)
                leg["model_dim"] = model_dim
                leg["num_heads"] = num_heads
                lm.append(leg)
            except Exception as e:
                lm.append({"seq_len": seq, "model_dim": model_dim,
                           "num_heads": num_heads,
                           "error": f"{type(e).__name__}: {e}"})
            gc.collect()
        for seq, steps in ((2048, 50), (8192, 25)):
            try:
                attn.append(_bench_attn(seq, steps=steps))
            except Exception as e:
                attn.append({"seq_len": seq, "error": f"{type(e).__name__}: {e}"})
            gc.collect()
        for l_local in (1024, 2048, 4096):
            try:
                ring.append(_bench_ring(l_local))
            except Exception as e:
                ring.append({"l_local": l_local,
                             "error": f"{type(e).__name__}: {e}"})
            gc.collect()
        out["lm"] = lm
        out["attn"] = attn
        out["ring"] = ring
        try:
            out["decode"] = _bench_decode()
        except Exception as e:
            out["decode"] = {"error": f"{type(e).__name__}: {e}"}
        gc.collect()
        try:
            out["feed"] = _bench_feed()
        except Exception as e:
            out["feed"] = {"error": f"{type(e).__name__}: {e}"}
        gc.collect()
        try:
            out["moe"] = _bench_moe()
        except Exception as e:
            out["moe"] = {"error": f"{type(e).__name__}: {e}"}
        gc.collect()
        try:
            out["pipeline"] = _bench_pipeline()
        except Exception as e:
            out["pipeline"] = {"error": f"{type(e).__name__}: {e}"}
        gc.collect()
        try:
            out["async"] = _bench_async()
        except Exception as e:
            out["async"] = {"error": f"{type(e).__name__}: {e}"}
        gc.collect()
        try:
            out["async_recovery"] = _bench_async_recovery()
        except Exception as e:
            out["async_recovery"] = {"error": f"{type(e).__name__}: {e}"}
        gc.collect()
        try:
            out["observability"] = _bench_observability()
        except Exception as e:
            out["observability"] = {"error": f"{type(e).__name__}: {e}"}
        gc.collect()
        try:
            out["health"] = _bench_health()
        except Exception as e:
            out["health"] = {"error": f"{type(e).__name__}: {e}"}
        gc.collect()
        try:
            out["embedding"] = _bench_embedding()
        except Exception as e:
            out["embedding"] = {"error": f"{type(e).__name__}: {e}"}
        _apply_leg_baselines(out, baseline)
    except Exception as e:
        out["value"] = 0.0  # contract: error lines carry the zero sentinel,
        out["vs_baseline"] = 0.0  # even if a sub-step already set a value
        out["error"] = f"{type(e).__name__}: {e}"
        out["traceback_tail"] = traceback.format_exc().strip().splitlines()[-3:]
    print(json.dumps(out))
    if _has_error(out):
        sys.exit(1)


if __name__ == "__main__":
    main()
