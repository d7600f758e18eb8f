"""Language-model workflow: train a tiny char-LM, then generate from it.

No reference counterpart (the reference predates transformers) — this is
the end-to-end demo of the framework's headroom path: TransformerLM
training (optionally dp-sharded over a mesh) followed by KV-cache
generation, all through the public API.

The corpus is synthetic but structured: arithmetic-progression "sentences"
over a small alphabet, so a 2-layer model learns real next-char structure
in seconds and greedy generation visibly continues the pattern (loss
falling + non-degenerate samples = the observable success criterion).

Usage:
    python -m distkeras_tpu.examples.lm_workflow --cpu 8     # 8-dev CPU mesh
    python -m distkeras_tpu.examples.lm_workflow             # the chip (refuses cpu)
    distkeras-lm                                             # console script
"""

from __future__ import annotations

import argparse


def _corpus(n_seqs: int, seq_len: int, vocab: int, seed: int):
    """Progressions c, c+d, c+2d, ... (mod vocab), one (start, step) per
    sequence: next-token is a deterministic function of the previous two."""
    import numpy as np

    rng = np.random.default_rng(seed)
    start = rng.integers(0, vocab, (n_seqs, 1))
    step = rng.integers(1, 5, (n_seqs, 1))
    pos = np.arange(seq_len + 1)[None, :]
    return ((start + step * pos) % vocab).astype(np.int32)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", type=int, default=0,
                        help="simulate this many CPU devices instead of real chips")
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--seq-len", type=int, default=32)
    parser.add_argument("--vocab", type=int, default=32)
    parser.add_argument("--model-dim", type=int, default=128)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--sample-len", type=int, default=24)
    parser.add_argument("--kv-heads", type=int, default=None,
                        help="grouped-query attention: KV head count "
                             "(default: = query heads, i.e. MHA); shrinks "
                             "the decode cache by the head ratio")
    args = parser.parse_args()
    if args.steps < 1:
        parser.error("--steps must be >= 1")

    from distkeras_tpu.platform import select_platform

    select_platform(args.cpu)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.decode import make_generate_fn
    from distkeras_tpu.models.transformer import small_lm_spec
    from distkeras_tpu.parallel.lm import (lm_data_shardings, lm_state_shardings,
                                           make_lm_train_step)
    from distkeras_tpu.parallel.mesh import create_nd_mesh

    devices = jax.devices()
    print(f"devices: {len(devices)} x {devices[0].device_kind}")
    dp = len(devices)

    # position budget: training needs seq_len; generation needs the
    # prompt half + sample_len, and the speculative demo additionally
    # writes k + 1 lookahead rows past the end (k = 4 below)
    # head_dim as close to the v5e-recommended 128 as divisibility allows
    # (see TransformerLM.num_heads): smallest head count that divides
    # model_dim with head_dim <= 128 — at the default 128-dim demo model
    # that is a single head
    num_heads = next(h for h in range(max(1, -(-args.model_dim // 128)),
                                      args.model_dim + 1)
                     if args.model_dim % h == 0 and args.model_dim // h <= 128)
    if args.kv_heads is not None and (
            args.kv_heads < 1 or num_heads % args.kv_heads):
        parser.error(f"--kv-heads {args.kv_heads} must be a positive divisor "
                     f"of the query head count {num_heads}")
    spec = small_lm_spec(vocab_size=args.vocab, model_dim=args.model_dim,
                         num_heads=num_heads,
                         num_kv_heads=args.kv_heads,
                         num_layers=args.layers,
                         max_seq_len=max(args.seq_len,
                                         args.seq_len // 2 + args.sample_len + 5))
    model = Model.init(spec, seed=0)
    opt = optax.adam(3e-3)

    mesh = create_nd_mesh((dp,), ("dp",))
    step = make_lm_train_step(spec, opt, mesh, sp_axis=None)
    psh, osh = lm_state_shardings(mesh, opt, model.params)
    dsh = lm_data_shardings(mesh)
    params = jax.device_put(jax.tree.map(jnp.asarray, model.params), psh)
    opt_state = jax.device_put(opt.init(params), osh)

    global_batch = args.batch_size * dp
    data = _corpus(global_batch * args.steps, args.seq_len, args.vocab, seed=1)
    first = last = None
    for i in range(args.steps):
        chunk = data[i * global_batch:(i + 1) * global_batch]
        tokens = jax.device_put(chunk[:, :-1], dsh)
        targets = jax.device_put(chunk[:, 1:], dsh)
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        if i == 0:
            first = float(loss)
        if i % 50 == 0:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    last = float(loss)
    print(f"loss {first:.3f} -> {last:.3f}")

    # generate: feed half a progression, the model must continue it
    trained = Model(spec=spec, params=jax.tree.map(np.asarray, params))
    gen = make_generate_fn(spec, args.sample_len)
    prompt = _corpus(2, args.seq_len, args.vocab, seed=99)[:, : args.seq_len // 2]
    out = np.asarray(gen(trained.params, jnp.asarray(prompt)))
    correct = 0
    for row, (p, o) in enumerate(zip(prompt, out)):
        d = int(p[1] - p[0]) % args.vocab
        want = [(int(p[-1]) + d * (i + 1)) % args.vocab for i in range(args.sample_len)]
        hits = sum(int(a) == b for a, b in zip(o, want))
        correct += hits
        print(f"prompt {list(map(int, p[:6]))}...  generated {list(map(int, o[:8]))}... "
              f"({hits}/{args.sample_len} continuation hits)")
    acc = correct / (2 * args.sample_len)
    print(f"continuation accuracy: {acc:.2f}")

    # the rest of the serving family, same public API: beam search (width
    # 4, scores are true sequence logprobs) and speculative decoding with
    # the model as its own draft (every proposal accepted — the committed
    # tokens are the model's own greedy decode, here nearly deterministic
    # because the learned progression logits are sharp)
    from distkeras_tpu.models.beam import make_beam_search_fn
    from distkeras_tpu.models.speculative import make_speculative_generate_fn

    beam_toks, beam_scores = make_beam_search_fn(spec, args.sample_len,
                                                 beam_width=4)(
        trained.params, jnp.asarray(prompt))
    print(f"beam-4 best scores: {[round(float(s), 2) for s in beam_scores]}")
    spec_toks = np.asarray(make_speculative_generate_fn(spec, spec,
                                                        args.sample_len, k=4)(
        trained.params, trained.params, jnp.asarray(prompt)))
    spec_agree = float((spec_toks == out).mean())
    print(f"speculative (self-draft) vs greedy agreement: {spec_agree:.2f}")

    if last > first or acc < 0.5 or spec_agree < 0.9:
        print("WARNING: model did not learn the progression structure "
              "or a serving path diverged")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
