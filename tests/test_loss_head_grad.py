"""The chunked loss head takes its gradient in its forward sweep
(``tpudist.models.lm_utils.chunked_head_reduce``, a ``jax.custom_vjp``):
that gradient must be ``jax.grad`` of the full-logits loss, for every head
that rides the skeleton and however the loss is differentiated — and the
lowered programs must hold the three products a chunk that the mathematics
needs, and one where nothing is differentiated."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudist import mesh as mesh_lib
from tpudist.models import bert, lm_utils
from tpudist.models.gpt2 import GPT2
from tpudist.models.llama import Llama
from tpudist.train import (
    create_train_state, evaluate_lm, lm_loss, make_train_step,
)

VOCAB = 97
CHUNK = 7  # 32 (LM) and 16 (MLM) predicted positions: a ragged last chunk


class _Head:
    """One family's model, batch, its two losses (full logits / chunked)
    and the head as ``chunked_head_reduce`` takes it."""

    def __init__(self, kind, dtype):
        rng = np.random.Generator(np.random.PCG64(5))
        self.kind = kind
        if kind == "bert":
            self.model = bert.Bert(
                vocab_size=VOCAB, max_seq_len=32, hidden_dim=32, depth=1,
                num_heads=4, dtype=dtype)
            tokens = rng.integers(4, VOCAB, (8, 16)).astype(np.int32)
            self.batch = bert.mlm_transform(
                vocab_size=VOCAB, mask_id=3, seed=3)({"tokens": tokens})
            self.label_key = "targets"
            self.full = bert.mlm_forward(self.model)
            self.chunked = bert.mlm_forward(self.model, chunk=CHUNK)
            head = bert.MlmHead(dtype=dtype)
            self.logits_fn = bert.mlm_head_logits_fn(head)
            self.head_params = bert.mlm_head_params
        else:
            cls, extra = {
                "tied": (GPT2, {}),
                "llama_untied": (Llama, {"tie_embeddings": False}),
            }[kind]
            self.model = cls(
                vocab_size=VOCAB, max_seq_len=33, hidden_dim=32, depth=1,
                num_heads=4, dtype=dtype, **extra)
            self.batch = {
                "tokens": rng.integers(0, VOCAB, (8, 33)).astype(np.int32)}
            self.label_key = "tokens"
            self.full = lambda params, stats, batch: (lm_loss(
                self.model.apply({"params": params}, batch["tokens"],
                                 train=True), batch["tokens"]), stats)
            self.chunked = lm_utils.chunked_lm_forward(self.model, chunk=CHUNK)
            self.logits_fn = lm_utils.tied_head_logits_fn
            self.head_params = lm_utils.lm_head_weight
        self.params = jax.jit(
            lambda key, tokens: self.model.init(key, tokens, train=False)
        )(jax.random.key(0), jnp.asarray(self.batch["tokens"]))["params"]

    def hidden_and_targets(self, params):
        hidden = self.model.apply(
            {"params": params}, self.batch["tokens"], train=True,
            return_hidden=True)
        if self.kind == "bert":
            return hidden, self.batch["targets"]
        return hidden[:, :-1], self.batch["tokens"][:, 1:]

    def weighted(self, params, weight, *, chunk):
        """The head on its own under ``weight``: chunked, or (``chunk``
        None) on the whole sequence's logits at once."""
        hidden, targets = self.hidden_and_targets(params)
        head_params = self.head_params(params)
        if chunk:
            return lm_utils.chunked_head_reduce(
                self.logits_fn, head_params, hidden, targets, weight, chunk)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            self.logits_fn(head_params, hidden), targets)
        return jnp.sum(ce * weight)

    def stepped(self, forward_loss, **kw):
        """The parameters' change over one SGD step of ``make_train_step``
        at rate 1: minus the gradient the step took."""
        mesh = mesh_lib.create_mesh(devices=jax.devices()[:1])
        tx = optax.sgd(1.0)
        state = create_train_state(
            self.model, 0, jnp.asarray(self.batch["tokens"][:1]), tx,
            mesh=mesh)
        # the step donates its state: hand it a copy
        state = state.replace(
            params=jax.tree_util.tree_map(jnp.array, self.params))
        step = make_train_step(
            self.model, tx, mesh, input_key="tokens",
            label_key=self.label_key, forward_loss=forward_loss, **kw)
        new, _ = step(state, self.batch)
        return jax.tree_util.tree_map(
            lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32),
            new.params, self.params)


def _close(got, want, dtype):
    """Leaf by leaf: float32 to the digits, bf16 (whose full-logits path
    rounds other things than the chunked one) by the leaf's norm."""
    def leaf(path, a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(
                a, b, rtol=3e-5, atol=2e-6, err_msg=str(path))
        else:
            gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)
            assert gap < 4e-2, (path, gap)

    jax.tree_util.tree_map_with_path(leaf, got, want)


CASES = ["ragged", "masked", "scaled", "grad_accum2", "remat"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["tied", "llama_untied", "bert"])
def test_head_gradient_is_the_full_logits_gradient(kind, dtype, case):
    head = _Head(kind, dtype)
    if case in ("ragged", "scaled"):
        # a cotangent that is not 1: the backward rule's own scaling
        scale = 3.7 if case == "scaled" else 1.0
        got, want = (
            jax.jit(jax.grad(lambda p, f=f: scale * f(p, {}, head.batch)[0]))(
                head.params)
            for f in (head.chunked, head.full))
    elif case == "masked":
        # the head alone under weights that leave positions out (and whole
        # rows), the normaliser behind the sum: a cotangent of 1 / count
        hidden, _ = head.hidden_and_targets(head.params)
        rng = np.random.Generator(np.random.PCG64(11))
        mask = rng.random(hidden.shape[:2]) < 0.4
        mask[0] = False
        weight = jnp.asarray(mask, jnp.float32)
        got, want = (
            jax.jit(jax.grad(lambda p, c=c: head.weighted(
                p, weight, chunk=c) / weight.sum()))(head.params)
            for c in (CHUNK, None))
    else:
        kw = {"grad_accum": 2} if case == "grad_accum2" else {"remat": "full"}
        got, want = (head.stepped(f, **kw)
                     for f in (head.chunked, head.full))
    assert any(np.abs(np.asarray(x, np.float32)).max() > 1e-6
               for x in jax.tree_util.tree_leaves(want))
    _close(got, want, dtype)


def test_weights_and_targets_take_no_gradient_and_forward_mode_is_refused():
    head = _Head("tied", jnp.float32)
    hidden, targets = head.hidden_and_targets(head.params)
    weight = jnp.ones(hidden.shape[:2])
    loss = lambda w: lm_utils.chunked_ce_sum(
        head.head_params(head.params), hidden, targets, w, CHUNK)
    assert not np.asarray(jax.grad(loss)(weight)).any()
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(loss, (weight,), (weight,))


# -- the static counter: vocabulary-wide products in the lowered programs ----


def _vocab_products(text, vocab):
    """The ``dot_general`` lines of a lowered module that have the
    vocabulary among an operand's or the result's dimensions."""
    return [
        line for line in text.splitlines()
        if "stablehlo.dot_general" in line
        and re.search(rf"tensor<(?:\d+x)*{vocab}(?:x\d+)*x\w+>", line)]


def _lowered_cell_step(family_name):
    """The tiny configuration of a cell's family (``benchmarks/tests``)
    through the family's own ``build``, as the cells' steps are made."""
    import inspect

    from test_benchmark_contract import _family, _one_chip_mesh, _tiny

    config, traffic = _tiny(family_name)
    mesh = _one_chip_mesh()
    built = _family(family_name).build(config, traffic, mesh)
    model, tx = built["model"], built["tx"]
    rows, seq = traffic["per_chip_batch"], traffic["seq_len"]
    state = create_train_state(
        model, 0, jnp.zeros((1, seq), jnp.int32), tx, mesh=mesh)
    step_args = inspect.signature(make_train_step).parameters
    step = make_train_step(
        model, tx, mesh,
        **{k: v for k, v in built["fit"].items() if k in step_args})
    batch = {"tokens": np.zeros((rows, seq), np.int32)}
    if family_name == "bert":
        batch.update(targets=batch["tokens"],
                     mlm_mask=np.ones((rows, seq), bool))
    text = step.jitted.lower(state, step.stage(batch)).as_text(debug_info=True)
    return text, config["vocab_size"]


@pytest.mark.parametrize("family_name", ["gpt2", "bert"])
def test_lowered_train_step_holds_three_vocabulary_products(family_name):
    """Logits, ``dh``, ``dW`` — was four with the logits made again in the
    backward — all in the one scan under ``jvp(loss_head)``, and nothing of
    the head checkpointed: no logits outlive their chunk, none are made
    twice."""
    text, vocab = _lowered_cell_step(family_name)
    products = _vocab_products(text, vocab)
    assert len(products) == 3, products
    assert "rematted_computation" not in text
    assert not re.search(r'loc\("checkpoint[/"]', text)
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    assert any(p.endswith("jvp(loss_head)/while") for p in paths)
    assert not any(p.endswith("transpose(jvp(loss_head))/while")
                   for p in paths), "the head's backward holds no loop"


def test_lowered_evaluate_lm_holds_one_vocabulary_product(monkeypatch):
    """Nothing differentiates ``evaluate_lm(chunk=)``: its program is the
    plain sweep, one product a chunk."""
    model = GPT2(vocab_size=VOCAB, max_seq_len=33, hidden_dim=32, depth=1,
                 num_heads=4)
    mesh = mesh_lib.create_mesh(devices=jax.devices()[:1])
    state = create_train_state(
        model, 0, jnp.zeros((1, 33), jnp.int32), optax.sgd(1.0), mesh=mesh)
    lowered = []
    real_jit = jax.jit

    def spy(fn, *a, **kw):
        jitted = real_jit(fn, *a, **kw)

        def call(*args):
            lowered.append(jitted.lower(*args).as_text())
            return jitted(*args)

        return call

    monkeypatch.setattr(jax, "jit", spy)
    rng = np.random.Generator(np.random.PCG64(2))
    loader = [{"tokens": rng.integers(0, VOCAB, (4, 33)).astype(np.int32)}]
    scores = evaluate_lm(model, state, loader, mesh, chunk=CHUNK)
    monkeypatch.undo()
    assert np.isfinite(scores["loss"])
    heads = [t for t in lowered if _vocab_products(t, VOCAB)]
    assert heads and all(len(_vocab_products(t, VOCAB)) == 1 for t in heads)
