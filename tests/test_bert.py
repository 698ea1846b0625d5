"""BERT encoder family (tpudist/models/bert.py): bidirectional attention,
the 80/10/10 MLM corruption, the mlm_forward train-step contract, and TP
sharding metadata."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudist import mesh as mesh_lib
from tpudist.models.bert import Bert, mlm_forward, mlm_transform
from tpudist.train import create_train_state, make_train_step


def tiny_bert(**kw):
    kw.setdefault("vocab_size", 97)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("hidden_dim", 32)
    kw.setdefault("depth", 2)
    kw.setdefault("num_heads", 4)
    return Bert(**kw)


def test_logits_shape_and_finite():
    model = tiny_bert()
    tokens = jnp.asarray(
        np.random.Generator(np.random.PCG64(0)).integers(0, 97, (2, 16)),
        jnp.int32,
    )
    params = model.init(jax.random.key(0), tokens, train=False)["params"]
    logits = model.apply({"params": params}, tokens, train=False)
    assert logits.shape == (2, 16, 97)
    assert np.isfinite(np.asarray(logits)).all()


def test_attention_is_bidirectional():
    """Perturbing the LAST token must change the FIRST position's logits —
    the defining difference from the causal decoder families."""
    model = tiny_bert()
    rng = np.random.Generator(np.random.PCG64(1))
    tokens = rng.integers(0, 97, (1, 16)).astype(np.int32)
    params = model.init(jax.random.key(0), jnp.asarray(tokens), train=False)[
        "params"
    ]
    base = model.apply({"params": params}, jnp.asarray(tokens), train=False)
    flipped = tokens.copy()
    flipped[0, -1] = (flipped[0, -1] + 1) % 97
    out = model.apply({"params": params}, jnp.asarray(flipped), train=False)
    assert not np.allclose(
        np.asarray(base[0, 0]), np.asarray(out[0, 0])
    ), "first-position logits ignored the last token (causal leak)"


def test_mlm_transform_recipe():
    rng = np.random.Generator(np.random.PCG64(2))
    tokens = rng.integers(5, 90, (64, 128)).astype(np.int32)
    tr = mlm_transform(vocab_size=97, mask_id=3, seed=0)
    out = tr({"tokens": tokens})
    sel = out["mlm_mask"]
    np.testing.assert_array_equal(out["targets"], tokens)
    # unselected positions pass through untouched
    np.testing.assert_array_equal(out["tokens"][~sel], tokens[~sel])
    rate = sel.mean()
    assert 0.12 < rate < 0.18, f"selection rate {rate} far from 0.15"
    masked_share = (out["tokens"][sel] == 3).mean()
    assert 0.7 < masked_share < 0.9, f"mask share {masked_share} not ~0.8"
    # ~10% of selected keep their identity
    kept = (out["tokens"][sel] == tokens[sel]).mean()
    assert 0.04 < kept < 0.2, f"keep share {kept} not ~0.1"
    # deterministic stream given the seed
    out2 = mlm_transform(vocab_size=97, mask_id=3, seed=0)({"tokens": tokens})
    np.testing.assert_array_equal(out["tokens"], out2["tokens"])


def test_mlm_training_learns():
    """A tiny BERT on a structured corpus (token i+1 follows token i, so
    context pins every masked identity) must cut its MLM loss sharply."""
    from tpudist.data.loader import DataLoader

    mesh = mesh_lib.create_mesh()
    model = tiny_bert(hidden_dim=64)
    # 4 distinct consecutive-run windows: any unmasked neighbor pins every
    # masked identity, so the loss must fall fast
    starts = np.array([0, 16, 32, 48]).repeat(64)
    windows = (starts[:, None] + np.arange(16)[None, :]) % 64 + 5
    data = {"tokens": windows.astype(np.int32)}
    loader = DataLoader(
        data, 32, transform=mlm_transform(vocab_size=97, mask_id=3, seed=1)
    )
    tx = optax.adam(3e-3)
    state = create_train_state(
        model, 0, jnp.zeros((1, 16), jnp.int32), tx, mesh
    )
    step = make_train_step(
        model, tx, mesh, input_key="tokens", label_key="targets",
        forward_loss=mlm_forward(model),
    )
    losses = []
    # post-LN BERT warms up slowly: it learns the marginal distribution
    # (ln 64 ≈ 4.16) in tens of steps but needs a couple hundred to use
    # context; 30 epochs × 8 batches ≈ 75 s on the 8-device CPU mesh
    for _ in range(30):
        for batch in loader:
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])


def test_tensor_parallel_metadata_shards_params():
    mesh = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=4, tensor=2))
    model = tiny_bert(vocab_size=96)  # divisible by the tensor axis
    state = create_train_state(
        model, 0, jnp.zeros((1, 16), jnp.int32), optax.adam(1e-3), mesh
    )
    wte = state.params["wte"]
    assert wte.sharding.spec[0] == mesh_lib.TENSOR_AXIS  # vocab-sharded
    qkv = state.params["h_0"]["qkv"]["kernel"]
    assert qkv.sharding.spec[2] == mesh_lib.TENSOR_AXIS  # column-parallel
    step = make_train_step(
        model, optax.adam(1e-3), mesh, input_key="tokens",
        label_key="targets", forward_loss=mlm_forward(model),
        state_sharding=jax.tree_util.tree_map(lambda x: x.sharding, state),
    )
    rng = np.random.Generator(np.random.PCG64(4))
    tokens = rng.integers(0, 96, (8, 16)).astype(np.int32)
    batch = mlm_transform(vocab_size=96, mask_id=3, seed=2)(
        {"tokens": tokens}
    )
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_chunked_mlm_forward_matches_full():
    """mlm_forward(chunk=...) must reproduce the full-logits loss exactly
    (same head math through MlmHead, bounded [B, chunk, V] live logits) —
    including the ragged final chunk."""
    from flax.core import FrozenDict

    from tpudist.models.bert import mlm_forward, mlm_transform

    model = tiny_bert()
    rng = np.random.Generator(np.random.PCG64(7))
    tokens = rng.integers(0, 97, (4, 16)).astype(np.int32)
    batch = {
        k: jnp.asarray(v)
        for k, v in mlm_transform(vocab_size=97, mask_id=3, seed=3)(
            {"tokens": tokens}
        ).items()
    }
    params = model.init(jax.random.key(0), batch["tokens"], train=False)[
        "params"
    ]
    full, _ = mlm_forward(model)(params, FrozenDict(), batch)
    chunked, _ = mlm_forward(model, chunk=5)(params, FrozenDict(), batch)
    np.testing.assert_allclose(
        np.asarray(chunked), np.asarray(full), rtol=1e-5, atol=1e-6
    )


def test_train_bert_example_e2e(tmp_path):
    """examples/train_bert.py end-to-end: memmap corpus -> MLM corruption ->
    fit -> masked eval, with the reserved [MASK] id above the corpus vocab."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "examples"))
    import train_bert

    binf = tmp_path / "corpus.bin"
    np.frombuffer(b"the quick brown fox jumps over the lazy dog. " * 400,
                  np.uint8).astype(np.uint16).tofile(binf)
    state, losses = train_bert.main([
        "--tokens", str(binf), "--vocab_size", "256", "--seq_len", "32",
        "--batch_size", "2", "--hidden_dim", "32", "--depth", "1",
        "--num_heads", "2", "--epochs", "2", "--lr", "3e-3",
        "--no_profiler", "--log_dir", str(tmp_path), "--JobID", "BertE2E",
        "--eval", "--chunked_ce", "16",
    ])
    assert len(losses) > 0 and np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # the reserved mask id extends the vocab by one
    assert state.params["wte"].shape[0] == 257


def test_train_bert_init_hf_warm_start(tmp_path):
    """--init_hf warm-starts from a local HF BertForMaskedLM checkpoint
    through tpudist.interop (sizes from flags, tokenizer's own [MASK] id)."""
    import sys
    from pathlib import Path

    import pytest

    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from safetensors.torch import save_file

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "examples"))
    import train_bert

    cfg = transformers.BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=32, type_vocab_size=2,
    )
    torch.manual_seed(11)
    hf = transformers.BertForMaskedLM(cfg)
    ckpt = tmp_path / "hf"
    ckpt.mkdir()
    # clone() breaks the tied-tensor aliases safetensors refuses to save
    save_file(
        {k: v.clone().contiguous() for k, v in hf.state_dict().items()},
        str(ckpt / "model.safetensors"),
    )

    binf = tmp_path / "corpus.bin"
    rng = np.random.Generator(np.random.PCG64(12))
    # short corpus → ~30 steps at lr 1e-4: weights stay near the warm start
    rng.integers(0, 64, 2_000).astype(np.uint16).tofile(binf)
    state, losses = train_bert.main([
        "--tokens", str(binf), "--vocab_size", "64", "--mask_id", "3",
        "--init_hf", str(ckpt),
        "--seq_len", "32", "--batch_size", "2", "--hidden_dim", "32",
        "--depth", "1", "--num_heads", "2", "--epochs", "1",
        "--no_profiler", "--log_dir", str(tmp_path), "--JobID", "BertHF",
    ])
    assert len(losses) > 0 and np.isfinite(losses).all()
    # warm start actually took: wte equals the HF table, not a fresh init
    want = hf.state_dict()["bert.embeddings.word_embeddings.weight"].numpy()
    np.testing.assert_allclose(
        np.asarray(state.params["wte"])[: want.shape[0]], want, atol=2e-2
    )


def test_classifier_fine_tunes_on_token_presence():
    """BertClassifier learns a simple sequence-level rule (does token 7
    appear?) through the standard train step — the fine-tuning surface."""
    from tpudist.models.bert import BertClassifier

    mesh = mesh_lib.create_mesh()
    model = BertClassifier(
        num_labels=2, vocab_size=32, max_seq_len=16, hidden_dim=32,
        depth=1, num_heads=2,
    )
    rng = np.random.Generator(np.random.PCG64(9))
    tokens = rng.integers(8, 32, (256, 8)).astype(np.int32)
    put = rng.random(256) < 0.5
    tokens[put, 0] = 7  # the signal token
    labels = put.astype(np.int32)
    tx = optax.adam(3e-3)
    state = create_train_state(
        model, 0, jnp.zeros((1, 8), jnp.int32), tx, mesh
    )
    step = make_train_step(model, tx, mesh, input_key="tokens",
                           label_key="label")
    batch = {"tokens": tokens, "label": labels}
    losses = []
    for _ in range(30):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < 0.1, losses[-1]


def test_classifier_grafts_pretrained_encoder():
    from flax import linen as nn

    from tpudist.models.bert import BertClassifier, classifier_params_from_mlm

    kw = dict(vocab_size=32, max_seq_len=16, hidden_dim=32, depth=1,
              num_heads=2)
    pre = nn.meta.unbox(
        tiny_bert(**kw).init(
            jax.random.key(1), jnp.zeros((1, 8), jnp.int32), train=False
        )["params"]
    )
    cls = nn.meta.unbox(
        BertClassifier(num_labels=3, **kw).init(
            jax.random.key(2), jnp.zeros((1, 8), jnp.int32), train=False
        )["params"]
    )
    grafted = classifier_params_from_mlm(cls, pre)
    np.testing.assert_array_equal(
        np.asarray(grafted["bert"]["wte"]), np.asarray(pre["wte"])
    )
    # head stays fresh
    np.testing.assert_array_equal(
        np.asarray(grafted["classifier"]["kernel"]),
        np.asarray(cls["classifier"]["kernel"]),
    )
    # grafted tree still runs
    model = BertClassifier(num_labels=3, **kw)
    out = model.apply(
        {"params": grafted}, jnp.zeros((2, 8), jnp.int32), train=False
    )
    assert out.shape == (2, 3)


def test_ring_attention_matches_full_bidirectional():
    """Bidirectional ring attention (causal=False K/V rotation) must equal
    full attention exactly — same unrolled params, different impl."""
    kw = dict(vocab_size=64, max_seq_len=16, hidden_dim=32, depth=2,
              num_heads=4)
    mesh_sp = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=4, seq=2))
    rng = np.random.Generator(np.random.PCG64(13))
    tokens = jnp.asarray(rng.integers(0, 64, (8, 16)), jnp.int32)
    ref_model = Bert(**kw)
    params = ref_model.init(jax.random.key(3), tokens, train=False)["params"]
    want = ref_model.apply({"params": params}, tokens, train=False)
    ring_model = Bert(attn_impl="ring", mesh=mesh_sp, **kw)
    got = ring_model.apply({"params": params}, tokens, train=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_ulysses_matches_full_bidirectional():
    kw = dict(vocab_size=64, max_seq_len=16, hidden_dim=32, depth=2,
              num_heads=4)
    mesh_sp = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=4, seq=2))
    rng = np.random.Generator(np.random.PCG64(14))
    tokens = jnp.asarray(rng.integers(0, 64, (8, 16)), jnp.int32)
    ref_model = Bert(**kw)
    params = ref_model.init(jax.random.key(4), tokens, train=False)["params"]
    want = ref_model.apply({"params": params}, tokens, train=False)
    uly_model = Bert(attn_impl="ulysses", mesh=mesh_sp, **kw)
    got = uly_model.apply({"params": params}, tokens, train=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


@pytest.mark.slow  # spawns a fresh jax world (the repo's subprocess-test convention)
def test_ring_mlm_train_step_with_sequence_sharded_batch():
    """Subprocess-contained wrapper around the real test below: under
    heavy host contention this ring-collective step has twice SIGABRT'd
    inside XLA:CPU's runtime (an environment wart — the persistent-cache
    note in tests/conftest.py has the full diagnosis). In-process, that
    abort kills the entire pytest run and every result with it; contained,
    a crash is one retried (then failed) test. One retry absorbs the
    observed flake rate."""
    import os
    import subprocess
    import sys

    cmd = [
        sys.executable, "-m", "pytest", "-q", "-x",
        f"{__file__}::test_ring_mlm_subproc_impl",
    ]
    # the child runs CACHE-LESS: the abort is in the AOT round trip of
    # this program's cached executable (measured: 2/6 child runs abort
    # with the cache, 0/6 without; capping the ISA does not help), and
    # the child's cold compile of one tiny step is ~40s — bounded
    env = dict(
        os.environ, TPUDIST_SUBPROC_TEST="1", TPUDIST_NO_JAX_CACHE="1"
    )
    r = subprocess.run(
        cmd, capture_output=True, text=True, timeout=600, env=env
    )
    if r.returncode < 0 or r.returncode == 134:
        # killed by a signal (the SIGABRT this wrapper contains): retry
        # once, LOUDLY — the recovery must stay observable so a spreading
        # flake is noticed before both attempts die
        print(
            f"\nring MLM subprocess CRASHED (rc={r.returncode}) — the known "
            "XLA:CPU abort (tests/conftest.py); retrying once:\n"
            + r.stderr[-1500:],
            file=sys.stderr,
        )
        r = subprocess.run(
            cmd, capture_output=True, text=True, timeout=600, env=env
        )
    # an ordinary test failure (rc>0) reports immediately — retrying would
    # only mask a real regression and double the wall clock
    assert r.returncode == 0, (
        f"ring MLM subprocess failed (rc={r.returncode}):\n"
        + r.stdout[-2000:] + r.stderr[-2000:]
    )


@pytest.mark.subproc_only
def test_ring_mlm_subproc_impl():
    """Context-parallel MLM training: tokens/targets/mask sharded over the
    'seq' axis, ring attention inside the compiled step. Collected only
    inside the wrapper's subprocess (the subproc_only marker skips it in
    the parent run — tests/conftest.py)."""
    from jax.sharding import PartitionSpec as P

    mesh_sp = mesh_lib.create_mesh(mesh_lib.MeshConfig(data=4, seq=2))
    model = tiny_bert(max_seq_len=16, mesh=mesh_sp, attn_impl="ring")
    tx = optax.adam(1e-3)
    state = create_train_state(
        model, 0, jnp.zeros((8, 16), jnp.int32), tx, mesh_sp
    )
    bd = (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS)
    spec = P(bd, mesh_lib.SEQUENCE_AXIS)
    step = make_train_step(
        model, tx, mesh_sp, input_key="tokens", label_key="targets",
        forward_loss=mlm_forward(model),
        batch_spec={"tokens": spec, "targets": spec, "mlm_mask": spec},
        state_sharding=jax.tree_util.tree_map(lambda x: x.sharding, state),
    )
    rng = np.random.Generator(np.random.PCG64(15))
    tokens = rng.integers(0, 97, (8, 16)).astype(np.int32)
    batch = mlm_transform(vocab_size=97, mask_id=3, seed=5)({"tokens": tokens})
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_scan_layers_trains_with_stacked_params():
    mesh = mesh_lib.create_mesh()
    model = tiny_bert(depth=3, scan_layers=True, remat_layers=True)
    tx = optax.adam(1e-3)
    state = create_train_state(
        model, 0, jnp.zeros((1, 16), jnp.int32), tx, mesh
    )
    # one traced layer, params stacked [depth, ...]
    assert "hs" in state.params and "h_0" not in state.params
    qkv = state.params["hs"]["block"]["qkv"]["kernel"]
    assert qkv.shape[0] == 3 and qkv.ndim == 5
    step = make_train_step(
        model, tx, mesh, input_key="tokens", label_key="targets",
        forward_loss=mlm_forward(model),
    )
    rng = np.random.Generator(np.random.PCG64(16))
    tokens = rng.integers(0, 97, (8, 16)).astype(np.int32)
    batch = mlm_transform(vocab_size=97, mask_id=3, seed=6)({"tokens": tokens})
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_stack_layers_converts_unrolled_bert_to_scanned():
    """The shared stack_layers converter (lm_utils) moves an unrolled BERT
    checkpoint into the scan layout: identical logits from both models."""
    from flax import linen as nn

    from tpudist.models.lm_utils import stack_layers, unstack_layers

    kw = dict(vocab_size=64, max_seq_len=16, hidden_dim=32, depth=3,
              num_heads=4)
    rng = np.random.Generator(np.random.PCG64(17))
    tokens = jnp.asarray(rng.integers(0, 64, (2, 16)), jnp.int32)
    unrolled = Bert(**kw)
    params = nn.meta.unbox(
        unrolled.init(jax.random.key(5), tokens, train=False)["params"]
    )
    want = unrolled.apply({"params": params}, tokens, train=False)

    stacked = stack_layers(params, 3, prefix="h_", dest="hs")
    scanned = Bert(scan_layers=True, **kw)
    got = scanned.apply({"params": stacked}, tokens, train=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )
    # and back
    back = unstack_layers(stacked, prefix="h_", dest="hs")
    again = unrolled.apply({"params": back}, tokens, train=False)
    np.testing.assert_allclose(np.asarray(again), np.asarray(want), rtol=1e-6)


def test_attention_mask_excludes_padding():
    """A right-padded batch with attention_mask must produce the SAME hidden
    states on the real positions as the unpadded sequence: padded keys are
    out of every softmax, so position i's context is identical either way."""
    model = tiny_bert()
    rng = np.random.Generator(np.random.PCG64(21))
    short = rng.integers(0, 97, (2, 12)).astype(np.int32)
    params = model.init(jax.random.key(0), jnp.asarray(short), train=False)[
        "params"
    ]
    base = model.apply({"params": params}, jnp.asarray(short), train=False)
    # pad with junk ids the model HAS embeddings for — the mask, not the pad
    # value, must make them inert
    padded = np.concatenate(
        [short, rng.integers(0, 97, (2, 4)).astype(np.int32)], axis=1
    )
    mask = np.zeros((2, 16), np.int32)
    mask[:, :12] = 1
    out = model.apply(
        {"params": params}, jnp.asarray(padded), train=False,
        attention_mask=jnp.asarray(mask),
    )
    np.testing.assert_allclose(
        np.asarray(out[:, :12]), np.asarray(base), rtol=2e-5, atol=2e-5
    )
    # and without the mask the junk keys must bleed in (the failure the
    # mask exists to prevent)
    unmasked = model.apply({"params": params}, jnp.asarray(padded), train=False)
    assert not np.allclose(np.asarray(unmasked[:, :12]), np.asarray(base))


def test_attention_mask_scan_layers_matches_unrolled():
    """The mask rides nn.scan as a broadcast argument; scanned and unrolled
    layouts must agree on masked inputs (same per-layer params via
    stack_layers would be overkill — equality of masked-vs-short suffices)."""
    model = tiny_bert(depth=3, scan_layers=True)
    rng = np.random.Generator(np.random.PCG64(22))
    short = rng.integers(0, 97, (1, 10)).astype(np.int32)
    params = model.init(jax.random.key(1), jnp.asarray(short), train=False)[
        "params"
    ]
    base = model.apply({"params": params}, jnp.asarray(short), train=False)
    padded = np.concatenate(
        [short, rng.integers(0, 97, (1, 6)).astype(np.int32)], axis=1
    )
    mask = np.zeros((1, 16), np.int32)
    mask[:, :10] = 1
    out = model.apply(
        {"params": params}, jnp.asarray(padded), train=False,
        attention_mask=jnp.asarray(mask),
    )
    np.testing.assert_allclose(
        np.asarray(out[:, :10]), np.asarray(base), rtol=2e-5, atol=2e-5
    )


def test_classifier_accepts_attention_mask():
    from tpudist.models.bert import BertClassifier

    model = BertClassifier(
        num_labels=3, vocab_size=97, max_seq_len=32, hidden_dim=32,
        depth=2, num_heads=4,
    )
    rng = np.random.Generator(np.random.PCG64(23))
    short = rng.integers(0, 97, (2, 9)).astype(np.int32)
    variables = model.init(jax.random.key(0), jnp.asarray(short), train=False)
    base = model.apply(variables, jnp.asarray(short), train=False)
    padded = np.concatenate(
        [short, rng.integers(0, 97, (2, 7)).astype(np.int32)], axis=1
    )
    mask = np.zeros((2, 16), np.int32)
    mask[:, :9] = 1
    out = model.apply(
        variables, jnp.asarray(padded), train=False,
        attention_mask=jnp.asarray(mask),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(base), rtol=2e-5, atol=2e-5
    )


def test_mlm_random_replacement_never_injects_mask_id():
    """The 10% random-token replacement draws from the vocab EXCLUDING
    [MASK]: a random draw landing on mask_id would create a target-bearing
    position the model can only see as masked."""
    rng = np.random.Generator(np.random.PCG64(24))
    tokens = rng.integers(0, 5, (512, 64)).astype(np.int32)
    # random_rate=1.0: every selected position becomes a random token, so a
    # single mask_id anywhere among them is the bug
    tr = mlm_transform(
        vocab_size=5, mask_id=3, random_rate=1.0, keep_rate=0.0, seed=0
    )
    out = tr({"tokens": tokens})
    sel = out["mlm_mask"]
    assert sel.sum() > 1000
    replaced = out["tokens"][sel]
    assert not (replaced == 3).any(), "random replacement produced [MASK]"
    # the other ids all remain reachable
    assert set(np.unique(replaced)) == {0, 1, 2, 4}
