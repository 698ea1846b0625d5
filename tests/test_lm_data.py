"""Memmap LM token dataset tests — the config-5 (OpenWebText-scale) input
path: lazy window gather, .bin/.npy formats, shard semantics."""

import numpy as np
import pytest

from tpudist.data.lm import TokenWindowLoader, encode_bytes, load_token_stream


@pytest.fixture(scope="module")
def stream():
    rng = np.random.Generator(np.random.PCG64(0))
    return rng.integers(0, 50257, 10_000).astype(np.uint16)


def test_load_npy_and_bin_roundtrip(tmp_path, stream):
    npy = tmp_path / "t.npy"
    np.save(npy, stream)
    binf = tmp_path / "t.bin"
    stream.tofile(binf)
    a = load_token_stream(npy)
    b = load_token_stream(binf, dtype=np.uint16)
    np.testing.assert_array_equal(np.asarray(a), stream)
    np.testing.assert_array_equal(np.asarray(b), stream)
    # memmaps, not copies
    assert isinstance(b, np.memmap)


def test_bad_suffix_and_shape(tmp_path, stream):
    with pytest.raises(ValueError):
        load_token_stream(tmp_path / "t.tokens")
    bad = tmp_path / "twod.npy"
    np.save(bad, stream.reshape(100, 100))
    with pytest.raises(ValueError):
        load_token_stream(bad)


def test_windows_cover_stream_without_overlap(stream):
    loader = TokenWindowLoader(stream, 4, 128, shuffle=False)
    assert loader.num_windows == len(stream) // 128  # 78
    batches = list(loader)
    assert len(batches) == len(loader) == 78 // 4
    flat = np.concatenate([b["tokens"].ravel() for b in batches])
    np.testing.assert_array_equal(flat, stream[: len(flat)].astype(np.int32))


def test_targets_in_window_adds_boundary_token(stream):
    loader = TokenWindowLoader(
        stream, 2, 64, targets_in_window=True, shuffle=False
    )
    b = next(iter(loader))
    assert b["tokens"].shape == (2, 65)
    # consecutive windows share the boundary token: last target of window k
    # is the first input of window k+1
    assert b["tokens"][0, -1] == b["tokens"][1, 0]


def test_memmap_gather_reads_lazily(tmp_path):
    big = tmp_path / "big.bin"
    n = 2_000_000
    (np.arange(n, dtype=np.int64) % 65536).astype(np.uint16).tofile(big)
    loader = TokenWindowLoader(big, 2, 1024, shuffle=False)
    b = loader.gather(np.array([0, 1000]))
    assert b["tokens"].shape == (2, 1024)
    np.testing.assert_array_equal(b["tokens"][0], np.arange(1024))
    np.testing.assert_array_equal(
        b["tokens"][1], np.arange(1000 * 1024, 1000 * 1024 + 1024) % 65536
    )


def test_sharded_windows_disjoint(stream):
    loaders = [
        TokenWindowLoader(stream, 4, 100, num_replicas=2, rank=r, seed=1)
        for r in range(2)
    ]
    s0 = set(loaders[0].sampler.epoch_indices().tolist())
    s1 = set(loaders[1].sampler.epoch_indices().tolist())
    assert not (s0 & s1)
    assert s0 | s1 == set(range(loaders[0].num_windows))


def test_iter_from_resume(stream):
    loader = TokenWindowLoader(stream, 8, 64, seed=5)
    full = list(loader)
    tail = list(loader.iter_from(3))
    for a, b in zip(full[3:], tail):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])


def test_vocab_guard_catches_out_of_range_tokens():
    """Out-of-range ids raise at gather time instead of letting XLA's
    embedding lookup clamp them and train silently on wrong vectors."""
    bad = np.array([0, 1, 2, 999, 4, 5, 6, 7] * 32, np.int32)
    loader = TokenWindowLoader(bad, 2, 8, vocab_size=256, shuffle=False)
    with pytest.raises(ValueError, match="token id 999"):
        list(loader)
    ok = TokenWindowLoader(bad % 256, 2, 8, vocab_size=256, shuffle=False)
    assert len(list(ok)) == len(ok)


def test_too_short_stream_raises():
    with pytest.raises(ValueError):
        TokenWindowLoader(np.arange(10, dtype=np.int32), 1, 64)


def test_encode_bytes():
    t = encode_bytes("hi\x00")
    np.testing.assert_array_equal(t, [104, 105, 0])
    assert t.dtype == np.int32


def test_train_gpt2_example_runs_with_bin_tokens(tmp_path):
    """End-to-end: the GPT-2 example trains from a raw .bin memmap."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "examples"))
    import train_gpt2

    rng = np.random.Generator(np.random.PCG64(3))
    binf = tmp_path / "corpus.bin"
    rng.integers(0, 256, 40_000).astype(np.uint16).tofile(binf)
    state, losses = train_gpt2.main([
        "--tokens", str(binf), "--vocab_size", "256", "--seq_len", "64",
        "--batch_size", "1", "--hidden_dim", "32", "--depth", "1",
        "--num_heads", "2", "--epochs", "1", "--no_profiler",
        "--log_dir", str(tmp_path), "--warmup_steps", "2",
    ])
    assert len(losses) > 0 and np.isfinite(losses).all()
