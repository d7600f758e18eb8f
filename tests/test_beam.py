"""Beam-search decoding: width-1 == greedy, score correctness, beam
dominance over greedy, EOS freezing, length penalty, guards."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models.base import Model
from distkeras_tpu.models.beam import beam_search, make_beam_search_fn
from distkeras_tpu.models.decode import generate
from distkeras_tpu.models.transformer import small_lm_spec


def _spec(**kw):
    cfg = dict(vocab_size=23, model_dim=32, num_heads=2, num_layers=2,
               max_seq_len=32)
    cfg.update(kw)
    spec = small_lm_spec(**cfg)
    spec.config["compute_dtype"] = "float32"  # tight parity tolerances
    return spec


@pytest.fixture(scope="module")
def model():
    return Model.init(_spec(), seed=11)


def _sequence_logprob(model, prompt, tokens):
    """Ground-truth total logprob of ``tokens`` continuing ``prompt``,
    via the O(L^2) full-forward (no cache): the number beam scores must
    reproduce."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(tokens)], axis=1)
    total = np.zeros(seq.shape[0], np.float32)
    for t in range(prompt.shape[1], seq.shape[1]):
        logits = model.apply(jnp.asarray(seq[:, :t]))[:, -1]
        logp = np.asarray(jax.nn.log_softmax(logits.astype(jnp.float32)))
        total += logp[np.arange(seq.shape[0]), seq[:, t]]
    return total


def test_beam_width_1_is_greedy(model):
    prompt = jnp.asarray([[5, 17, 3], [2, 2, 9]], jnp.int32)
    want = np.asarray(generate(model, prompt, max_new_tokens=6))
    got, scores = beam_search(model, prompt, 6, beam_width=1)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_allclose(np.asarray(scores),
                               _sequence_logprob(model, prompt, want),
                               rtol=1e-4, atol=1e-4)


def test_beam_scores_are_true_logprobs_and_beat_greedy(model):
    """Every returned beam's score must equal the sequence's true total
    logprob under the model, and the best beam must score >= the greedy
    sequence.  (The dominance half is NOT a theorem — beam search can
    prune greedy's continuation and end worse, observed on an 8k-vocab
    model on TPU — but it holds on this fixed seed/model/prompt, where
    it pins that the search actually explores rather than degenerating
    to width 1.)"""
    prompt = jnp.asarray([[7, 1, 19]], jnp.int32)
    fn = make_beam_search_fn(model.spec, 5, beam_width=4, return_all=True)
    toks, scores = fn(model.params, prompt)
    toks, scores = np.asarray(toks), np.asarray(scores)
    assert toks.shape == (1, 4, 5) and scores.shape == (1, 4)
    assert (np.diff(scores[0]) <= 1e-6).all(), "beams not sorted best-first"
    for wi in range(4):
        true = _sequence_logprob(model, prompt, toks[:, wi])
        np.testing.assert_allclose(scores[:, wi], true, rtol=1e-4, atol=1e-4)
    greedy = np.asarray(generate(model, prompt, max_new_tokens=5))
    g_score = _sequence_logprob(model, prompt, greedy)
    assert scores[0, 0] >= g_score[0] - 1e-4


def test_beam_eos_freezes_and_pads(model):
    """Declare the best beam's 2nd token as EOS: that beam must keep the
    EOS, pad afterwards, and report only the pre-EOS score."""
    prompt = jnp.asarray([[4, 12]], jnp.int32)
    free, _ = beam_search(model, prompt, 6, beam_width=3)
    eos = int(np.asarray(free)[0, 1])
    toks, scores = beam_search(model, prompt, 6, beam_width=3, eos_id=eos,
                               pad_id=0)
    toks = np.asarray(toks)
    hits = np.where(toks[0] == eos)[0]
    if hits.size:  # the winning beam may legitimately avoid EOS entirely
        first = hits[0]
        assert np.all(toks[0, first + 1:] == 0), toks
        clipped = toks[:, :first + 1]
        np.testing.assert_allclose(
            np.asarray(scores),
            _sequence_logprob(model, prompt, clipped), rtol=1e-4, atol=1e-4)


def test_length_penalty_changes_ranking_monotonically(model):
    """With alpha > 0 scores are divided by the GNMT factor: reported
    scores must equal raw scores normalized by each beam's length."""
    prompt = jnp.asarray([[3, 3, 14]], jnp.int32)
    raw_t, raw_s = make_beam_search_fn(model.spec, 4, beam_width=3,
                                       return_all=True)(model.params, prompt)
    pen_t, pen_s = make_beam_search_fn(model.spec, 4, beam_width=3,
                                       length_penalty=1.0,
                                       return_all=True)(model.params, prompt)
    # same beam set (no EOS -> all lengths 4): penalty divides uniformly,
    # so the ranking and members must match and scores scale by (9/6)
    np.testing.assert_array_equal(np.asarray(raw_t), np.asarray(pen_t))
    np.testing.assert_allclose(np.asarray(pen_s),
                               np.asarray(raw_s) / 1.5, rtol=1e-5)


def test_beam_guards(model):
    with pytest.raises(ValueError, match="beam_width"):
        make_beam_search_fn(model.spec, 4, beam_width=0)
    with pytest.raises(ValueError, match="max_seq_len"):
        beam_search(model, jnp.zeros((1, 30), jnp.int32), 10)
    with pytest.raises(ValueError, match="eos_id"):
        make_beam_search_fn(model.spec, 4, eos_id=99)
    sharded = _spec(seq_axis="sp")
    with pytest.raises(ValueError, match="plain"):
        make_beam_search_fn(sharded, 4)
