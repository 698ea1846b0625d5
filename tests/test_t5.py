"""T5 encoder-decoder family (tpudist.models.t5): span corruption
invariants, decoder causality, cross-attention liveness, and the compiled
train step learning a deterministic denoising task."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudist.models.t5 import (
    T5, seq2seq_forward, span_corrupt_transform, span_corruption_plan,
)

_CFG = dict(vocab_size=64, hidden_dim=32, ffn_dim=64, enc_depth=2,
            dec_depth=2, num_heads=4)


def _toy_batch(b=4, length=32, vocab_floor=40, seed=0):
    rng = np.random.Generator(np.random.PCG64(seed))
    # data ids stay below the sentinel/EOS range near vocab_size
    return {"tokens": rng.integers(1, vocab_floor, (b, length)).astype(np.int32)}


def test_span_corruption_shapes_and_reconstruction():
    length = 32
    noise, spans, enc_len, dec_len = span_corruption_plan(length)
    t = span_corrupt_transform(64, seed=3)
    batch = _toy_batch(length=length)
    out = t(batch)
    assert out["enc_tokens"].shape == (4, enc_len)
    assert out["dec_tokens"].shape == (4, dec_len)
    assert out["targets"].shape == (4, dec_len)
    sentinels = 64 - 1 - np.arange(spans)
    eos = 64 - spans - 1
    for i in range(4):
        enc, tgt, dec = out["enc_tokens"][i], out["targets"][i], out["dec_tokens"][i]
        # every sentinel appears exactly once on each side, in order
        assert [s for s in enc if s in sentinels] == list(sentinels)
        assert [s for s in tgt if s in sentinels] == list(sentinels)
        assert tgt[-1] == eos
        # decoder input = target shifted right behind the start id
        assert dec[0] == 0
        np.testing.assert_array_equal(dec[1:], tgt[:-1])
        # splicing the target's spans back into the encoder's gaps
        # reconstructs the original window exactly
        rebuilt = []
        tpos = 0
        for tok in enc:
            if tok in sentinels:
                tpos += 1  # skip the sentinel in the target stream
                while tpos < len(tgt) and tgt[tpos] not in sentinels and tgt[tpos] != eos:
                    rebuilt.append(int(tgt[tpos]))
                    tpos += 1
            else:
                rebuilt.append(int(tok))
        np.testing.assert_array_equal(rebuilt, batch["tokens"][i])


def test_span_corruption_keying_fresh_per_epoch_and_resume_exact():
    """The corruption stream is keyed (seed, epoch, start): same window in
    different epochs draws DIFFERENT corruptions; the same (epoch, start)
    replays identically (mid-epoch resume); and the position-less fallback
    (foreign loaders) is deterministic in the batch contents."""
    t = span_corrupt_transform(64, seed=3)
    assert t.wants_position
    batch = _toy_batch()
    e0 = t(batch, 0, 0)
    e0_again = t(batch, 0, 0)  # resume replay
    e1 = t(batch, 1, 0)        # next epoch, same window
    b1 = t(batch, 0, 4)        # same epoch, next batch position
    np.testing.assert_array_equal(e0["enc_tokens"], e0_again["enc_tokens"])
    np.testing.assert_array_equal(e0["targets"], e0_again["targets"])
    assert not np.array_equal(e0["enc_tokens"], e1["enc_tokens"])
    assert not np.array_equal(e0["enc_tokens"], b1["enc_tokens"])
    # position-less fallback: content-keyed, deterministic
    f0, f1 = t(batch), t(batch)
    np.testing.assert_array_equal(f0["enc_tokens"], f1["enc_tokens"])

    # and the TokenWindowLoader actually passes (epoch, start): two epochs
    # over an unshuffled stream corrupt the same windows differently
    from tpudist.data.lm import TokenWindowLoader

    stream = np.arange(200, dtype=np.int32) % 40
    loader = TokenWindowLoader(
        stream, 4, 32, vocab_size=40, shuffle=False, transform=t
    )
    loader.sampler.set_epoch(0)
    first = next(iter(loader))
    loader.sampler.set_epoch(1)
    second = next(iter(loader))
    assert not np.array_equal(first["enc_tokens"], second["enc_tokens"])


def test_decoder_is_causal_and_uses_encoder():
    model = T5(**_CFG)
    rng = np.random.Generator(np.random.PCG64(0))
    enc = jnp.asarray(rng.integers(1, 40, (2, 12)), jnp.int32)
    dec = jnp.asarray(rng.integers(1, 40, (2, 8)), jnp.int32)
    params = model.init(jax.random.key(0), enc, dec)
    logits = model.apply(params, enc, dec, train=False)
    assert logits.shape == (2, 8, 64) and logits.dtype == jnp.float32

    # causality: perturbing a future decoder token leaves earlier logits
    # bit-identical
    dec2 = dec.at[:, 5].set((dec[:, 5] + 7) % 40)
    logits2 = model.apply(params, enc, dec2, train=False)
    np.testing.assert_array_equal(
        np.asarray(logits[:, :5]), np.asarray(logits2[:, :5])
    )
    assert (np.asarray(logits[:, 5:]) != np.asarray(logits2[:, 5:])).any()

    # cross-attention liveness: changing the ENCODER input moves the
    # decoder logits everywhere
    enc2 = enc.at[:, 0].set((enc[:, 0] + 3) % 40)
    logits3 = model.apply(params, enc2, dec, train=False)
    assert (np.asarray(logits) != np.asarray(logits3)).all(axis=-1).any()


def test_relative_bias_makes_encoder_order_matter():
    """Swapping two encoder tokens must move the decoder logits: without
    the relative position bias the encoder stack is permutation-
    equivariant and cross-attention (a sum over keys) would erase the
    swap entirely — the bias is the model's only position signal."""
    model = T5(**_CFG)
    enc = jnp.asarray(np.arange(1, 11)[None, :], jnp.int32)
    dec = jnp.asarray(np.arange(11, 17)[None, :], jnp.int32)
    params = model.init(jax.random.key(1), enc, dec)
    logits = np.asarray(model.apply(params, enc, dec, train=False))
    swapped = enc.at[0, 2].set(enc[0, 3]).at[0, 3].set(enc[0, 2])
    logits_sw = np.asarray(model.apply(params, swapped, dec, train=False))
    assert not np.allclose(logits, logits_sw)


def test_t5_incremental_decode_matches_full_forward():
    """Step-by-step cached decode reproduces the teacher-forced joint
    forward exactly — pins the decoder KV cache, the position-sliced
    relative bias row, and the per-step cross-attention."""
    model = T5(**_CFG, max_decode_len=16)
    rng = np.random.Generator(np.random.PCG64(0))
    enc = jnp.asarray(rng.integers(1, 40, (2, 12)), jnp.int32)
    dec = jnp.asarray(rng.integers(1, 40, (2, 8)), jnp.int32)
    params = model.init(jax.random.key(0), (enc, dec), train=False)["params"]
    full = np.asarray(model.apply({"params": params}, enc, dec, train=False))

    enc_out = model.apply(
        {"params": params}, enc, train=False, encode_only=True
    )
    cache = model.init(
        jax.random.key(0), jnp.zeros((2, 1), jnp.int32), train=False,
        decode=True, enc=jnp.zeros((2, 1, model.hidden_dim), enc_out.dtype),
    )["cache"]
    steps = []
    for t in range(dec.shape[1]):
        logits, upd = model.apply(
            {"params": params, "cache": cache}, dec[:, t:t + 1],
            train=False, decode=True, enc=enc_out, mutable=["cache"],
        )
        cache = upd["cache"]
        steps.append(np.asarray(logits[:, 0]))
    incremental = np.stack(steps, axis=1)
    np.testing.assert_allclose(incremental, full, atol=2e-4, rtol=2e-4)

    # multi-token CHUNK decode (bulk prefill shape): first 5 tokens in one
    # pass, remainder stepwise — pins the per-row bias slice and the
    # causal-within-chunk cache mask
    cache = model.init(
        jax.random.key(0), jnp.zeros((2, 1), jnp.int32), train=False,
        decode=True, enc=jnp.zeros((2, 1, model.hidden_dim), enc_out.dtype),
    )["cache"]
    chunk_logits, upd = model.apply(
        {"params": params, "cache": cache}, dec[:, :5],
        train=False, decode=True, enc=enc_out, mutable=["cache"],
    )
    cache = upd["cache"]
    np.testing.assert_allclose(
        np.asarray(chunk_logits), full[:, :5], atol=2e-4, rtol=2e-4
    )
    logits, _ = model.apply(
        {"params": params, "cache": cache}, dec[:, 5:6],
        train=False, decode=True, enc=enc_out, mutable=["cache"],
    )
    np.testing.assert_allclose(
        np.asarray(logits[:, 0]), full[:, 5], atol=2e-4, rtol=2e-4
    )


def test_t5_decode_overrun_fails_loudly():
    """Past max_decode_len the bias dynamic_slice and the cache update
    would silently CLAMP (wrong biases, clobbered last slot):
    the decode path must fail loudly instead. Eager direct callers get a
    ValueError; a jitted decode loop gets NaN logits for the overrunning
    step (deterministic poison, not plausible-looking garbage)."""
    model = T5(**_CFG, max_decode_len=4)
    rng = np.random.Generator(np.random.PCG64(1))
    enc = jnp.asarray(rng.integers(1, 40, (2, 6)), jnp.int32)
    params = model.init(jax.random.key(0), (enc, enc), train=False)["params"]
    enc_out = model.apply(
        {"params": params}, enc, train=False, encode_only=True
    )

    def fresh_cache():
        return model.init(
            jax.random.key(0), jnp.zeros((2, 1), jnp.int32), train=False,
            decode=True, enc=jnp.zeros((2, 1, model.hidden_dim),
                                       enc_out.dtype),
        )["cache"]

    tok = jnp.ones((2, 1), jnp.int32)

    def step(cache):
        logits, upd = model.apply(
            {"params": params, "cache": cache}, tok,
            train=False, decode=True, enc=enc_out, mutable=["cache"],
        )
        return logits, upd["cache"]

    # a chunk longer than the buffer is a static, immediate refusal
    with pytest.raises(ValueError, match="max_decode_len"):
        model.apply(
            {"params": params, "cache": fresh_cache()},
            jnp.ones((2, 5), jnp.int32),
            train=False, decode=True, enc=enc_out, mutable=["cache"],
        )

    # eager incremental decode: 4 steps fill the buffer, the 5th raises
    cache = fresh_cache()
    for _ in range(4):
        logits, cache = step(cache)
        assert np.isfinite(np.asarray(logits)).all()
    with pytest.raises(ValueError, match="max_decode_len"):
        step(cache)

    # jitted loop (cursor is a tracer): the overrunning step's logits are
    # NaN — loud in any downstream use — while in-bounds steps stay finite
    jit_step = jax.jit(step)
    cache = fresh_cache()
    for i in range(5):
        logits, cache = jit_step(cache)
        finite = np.isfinite(np.asarray(logits)).all()
        assert finite == (i < 4), (i, finite)


def test_generate_seq2seq_greedy_matches_full_forward_rollout():
    """Greedy generate_seq2seq equals repeatedly argmaxing the joint
    teacher-forced forward — generation and training-path numerics agree
    end-to-end (the encoder-decoder twin of the GPT-2 greedy oracle)."""
    from tpudist.generate import generate_seq2seq

    model = T5(**_CFG, max_decode_len=16)
    rng = np.random.Generator(np.random.PCG64(1))
    enc = rng.integers(1, 40, (2, 10)).astype(np.int32)
    params = model.init(
        jax.random.key(1), (jnp.asarray(enc), jnp.zeros((2, 4), jnp.int32)),
        train=False,
    )["params"]

    out = generate_seq2seq(model, params, enc, 6, temperature=0.0)
    again = generate_seq2seq(model, params, enc, 6, temperature=0.0)
    np.testing.assert_array_equal(out, again)
    assert out.shape == (2, 6) and out.dtype == np.int32

    dec = np.zeros((2, 1), np.int32)  # start_id 0
    for _ in range(6):
        logits = model.apply(
            {"params": params}, jnp.asarray(enc), jnp.asarray(dec),
            train=False,
        )
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))[:, None]
        dec = np.concatenate([dec, nxt.astype(np.int32)], axis=1)
    np.testing.assert_array_equal(out, dec[:, 1:])

    with pytest.raises(ValueError, match="max_decode_len"):
        generate_seq2seq(model, params, enc, 16)


def test_train_step_learns_denoising():
    """The full compiled step (8-dev DP mesh) learns a deterministic
    sequence's span-filling: loss collapses toward zero."""
    from tpudist import mesh as mesh_lib
    from tpudist.train import create_train_state, make_train_step

    mesh = mesh_lib.create_mesh()
    model = T5(**_CFG)
    length = 32
    base = (np.arange(length) % 37 + 1).astype(np.int32)  # deterministic text
    tokens = np.tile(base, (16, 1))
    transform = span_corrupt_transform(64, seed=5)

    tx = optax.adam(1e-2)
    sample = transform({"tokens": tokens[:1]})
    state = create_train_state(
        model, 0,
        (jnp.asarray(sample["enc_tokens"]), jnp.asarray(sample["dec_tokens"])),
        tx, mesh,
    )
    step = make_train_step(
        model, tx, mesh, forward_loss=seq2seq_forward(model),
        input_key="enc_tokens", label_key="targets",
    )
    losses = []
    for i in range(80):
        batch = transform({"tokens": tokens})
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    # the spans move every step, so the task is "learn the fixed text";
    # a model that learns it collapses well below the ~3.6-nat entropy
    # of guessing tokens
    assert losses[-1] < 1.0 and losses[-1] < losses[0] * 0.25, losses[::10]
