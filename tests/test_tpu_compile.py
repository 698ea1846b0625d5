"""The Pallas kernels of the main path, compiled for a DESCRIBED TPU v5e.

No chip is attached here, but the TPU's compiler is installed and compiles
for a described topology: what Mosaic refuses (a slice off the tiling, too
much VMEM, an unsupported op) fails here at no chip time, where interpret
mode — all the rest of the suite ever runs — accepts anything. Each case
lowers a kernel at the widths ``chip_smoke.py`` runs it at, with
``interpret=False``, for one described chip, and asserts the compiled
program really holds the kernel (``tpu_custom_call``). A compile is not a
run: nothing here says the results are right or fast.

This is the ONE file that describes a topology, and it does so inside a
fixture: only one process may load libtpu, so a describe at import (or in
``conftest.py``, a ``skipif`` or a ``parametrize``) would make xdist's
workers collect different tests — and run none.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpudist import remat
from tpudist.parallel import ep
from tpudist.ops import backend
from tpudist.ops.decode import (
    _fused_decode_attention, paged_decode_attention,
)
from tpudist.ops.flash_attention import flash_attention
from tpudist.ops.fused_update import fused_leaf_update
from tpudist.ops.layernorm import fused_layernorm
from tpudist.ops.vmem_attention import vmem_attention

# a compile for a described chip is written to the persistent cache but can
# never be read back without the chip (it warns and recompiles): keep it out
pytestmark = pytest.mark.usefixtures("no_persistent_compile_cache")


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip, monkeypatch):
    """``compile_for_chip(fn, (shape, dtype), ...)`` -> compiled HLO text
    of ``fn`` on one described chip, kernels Mosaic-compiled: the backend
    helper sees this process's CPU, so the test answers for it."""
    monkeypatch.setattr(backend, "interpret", lambda: False)

    def run(fn, *shapes):
        args = [
            jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes
        ]
        return jax.jit(fn).lower(*args).compile().as_text()

    return run


def _expert_control_flow(jaxpr):
    """Name stacks of the ``cond`` and ``while`` equations of a jaxpr (the
    bodies of its loops, branches and custom rules included) that sit under
    a stage of the dropless expert layer."""
    found = []
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        if eqn.primitive.name in ("cond", "while") and "moe_" in stack:
            found.append(f"{stack}/{eqn.primitive.name}")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _expert_control_flow(sub)
    return found


def _expert_stages(lowered):
    """``{stage: ops}`` of a lowered step's name stacks that hold a
    ``moe_*`` component, by what the benchmark's reader makes of them
    (``layer_metrics/moe_ms.py`` ``stage_of``: the component right after
    the block's)."""
    import re

    from benchmarks.layer_metrics import moe_ms

    stages = {}
    for path in set(re.findall(r'loc\("([^"]+)"',
                               lowered.as_text(debug_info=True))):
        if re.search(r"/h_\d+/.*moe_", path):
            stage = moe_ms.stage_of(path, "%fusion = f32[]")
            stages[stage] = stages.get(stage, 0) + 1
    return stages


def _fwd_bwd(attn):
    """Forward + backward of an attention kernel as one program."""
    def f(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
    return f


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.mark.parametrize("shape,causal", [
    ((8, 1024, 12, 64), True),    # GPT-2 124M train step, batch 8/chip
    ((32, 197, 12, 64), False),   # ViT-B/16: 196 patches + cls, padded to 256
    ((8, 1024, 16, 64), True),    # the benchmark's GPT-2 medium cells
    ((16, 512, 16, 64), False),   # the benchmark's BERT-large cell
], ids=["gpt2_s1024_causal", "vit_s197", "gpt2_medium_cell", "bert_large_cell"])
def test_vmem_attention_fwd_bwd(compile_for_chip, shape, causal):
    hlo = compile_for_chip(
        _fwd_bwd(functools.partial(vmem_attention, causal=causal)),
        *[(shape, BF16)] * 3,
    )
    assert hlo.count("tpu_custom_call") >= 2  # forward and backward kernels


def test_vmem_attention_causal_gqa_fwd_bwd(compile_for_chip):
    """Llama-125M's 12:4 heads of 64 at S=1024: the blocked backward adds a
    head's f32 dk/dv into the output block the group revisits (a 64-wide
    view of that block is a slice Mosaic refuses)."""
    q, kv = ((8, 1024, 12, 64), BF16), ((8, 1024, 4, 64), BF16)
    hlo = compile_for_chip(
        _fwd_bwd(functools.partial(vmem_attention, causal=True)), q, kv, kv,
    )
    assert hlo.count("tpu_custom_call") >= 2


def test_flash_attention_fwd_bwd_s4096(compile_for_chip):
    hlo = compile_for_chip(
        _fwd_bwd(functools.partial(flash_attention, causal=True)),
        *[((2, 4096, 12, 64), BF16)] * 3,
    )
    assert "tpu_custom_call" in hlo


def test_flash_attention_block_diffusion_mask_fwd_bwd_2x4096(compile_for_chip):
    """The SDAR cell's attention call: 32 heads of 128 over 8,192 rows, the
    noised and the clean copy of 4,096 tokens under ``BlockMask(4, 4096)``
    — the mask's tile test on the ``program_id``s and its elementwise test
    on a 512 x 1024 tile (shifts, compares, and / or of boolean vectors:
    Mosaic has no select between them), forward and both Pallas backward
    kernels."""
    from tpudist.ops.attention import BlockMask

    q = ((1, 8192, 32, 128), BF16)
    hlo = compile_for_chip(
        _fwd_bwd(functools.partial(flash_attention, mask=BlockMask(4, 4096))),
        q, q, q)
    assert hlo.count("tpu_custom_call") >= 3  # forward, dkv, dq


@pytest.mark.parametrize("blocks", [(512, 512), (512, 1024)])
def test_flash_attention_sliding_window_fwd_bwd_s8192(compile_for_chip,
                                                      blocks):
    """The Laguna cell's sliding-window call: 8,192 rows under
    ``BlockMask(window=512)``, forward and both Pallas backward kernels on
    the band's grid — the inner block offset from the outer one and
    clamped by ``min`` in the index maps and the kernels — at the blocks
    ``default_blocks`` takes (512 x 512) and at 512 x 1024. One head: the
    kernels' code does not depend on the count."""
    from tpudist.ops.attention import BlockMask

    q = ((1, 8192, 1, 128), BF16)
    hlo = compile_for_chip(
        _fwd_bwd(functools.partial(
            flash_attention, mask=BlockMask(window=512), block_q=blocks[0],
            block_k=blocks[1])), q, q, q)
    assert hlo.count("tpu_custom_call") >= 3  # forward, dkv, dq


def test_flash_attention_keys_192_values_128_fwd_bwd_s8192(compile_for_chip):
    """The Kanana-2 cell's MLA call: 32 heads, keys of 192, values of 128,
    8192 tokens, the shape's own blocks and the Pallas backward. Mosaic
    takes the 192-wide blocks as they are: three kernels, and no operand
    of them padded to 256."""
    import re

    q, v = ((1, 8192, 32, 192), BF16), ((1, 8192, 32, 128), BF16)
    hlo = compile_for_chip(
        _fwd_bwd(functools.partial(flash_attention, causal=True)), q, q, v)
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 3, len(kernels)  # forward, dkv, dq
    widths = {int(w) for line in kernels
              for w in re.findall(r"bf16\[1,32,8192,(\d+)\]", line)}
    assert widths == {128, 192}, widths


@pytest.mark.parametrize("residual", [False, True], ids=["plain", "residual"])
def test_fused_layernorm_fwd_bwd(compile_for_chip, residual):
    def loss(x, y, scale, bias):
        out = fused_layernorm(
            x, scale, bias, residual=y if residual else None, eps=1e-5,
        )
        return sum(o.astype(jnp.float32).sum() for o in jax.tree.leaves(out))

    rows = ((8, 1024, 768), BF16)
    hlo = compile_for_chip(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
        rows, rows, ((768,), F32), ((768,), F32),
    )
    assert hlo.count("tpu_custom_call") >= 2  # forward and backward kernels


@pytest.mark.parametrize("shape", [
    (50257, 1024),       # GPT-2 medium's table: rows no tile divides
    (1024, 3, 16, 64),   # a qkv kernel, heads split: last dimension 64
    (16, 64, 1024),      # an out kernel
], ids=["wte_50257x1024", "qkv_1024x3x16x64", "out_16x64x1024"])
def test_fused_adamw_leaf_is_one_fusion_in_its_own_layout(
        compile_for_chip, shape):
    """The update of one leaf as the cells run it (bf16 gradient, f32
    moments and master, the bf16 copy, ``apply_updates``' add): the TPU
    compiler makes ONE fusion of it, whose results are the copy, the
    moments and the new master — the update itself never reaches HBM — and
    nothing lays the leaf out anew around it."""
    import re

    def update(g, m, v, p, lr, b1c, b2c):
        u, m2, v2, copy = fused_leaf_update(
            g, m, v, p, lr, b1c, b2c, b1=0.9, b2=0.999, eps=1e-8, wd=0.1,
            compute_dtype=BF16,
        )
        return p + u, m2, v2, copy

    leaf = (shape, F32)
    hlo = compile_for_chip(update, (shape, BF16), leaf, leaf, leaf,
                           *[((), F32)] * 3)
    entry = hlo[hlo.index("ENTRY"):]
    # (result type, op) of every instruction that yields an array
    ops = [op for typ, op in re.findall(
        r"^\s+(?:ROOT )?\S+ = (.*?) ([\w\-]+)\(", entry, re.M
    ) if re.search(r"\[\d", typ)]
    assert ops.count("fusion") == 1, ops
    assert not {"copy", "reshape", "transpose", "pad", "slice",
                "custom-call"} & set(ops), ops


def test_fused_decode_attention_b16_s1024(compile_for_chip):
    hlo = compile_for_chip(
        _fused_decode_attention,
        ((16, 1, 12, 64), BF16), ((16, 12, 1024, 64), BF16),
        ((16, 12, 1024, 64), BF16), ((), I32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows", [1, 4], ids=["decode_tick", "verify_chunk4"])
def test_paged_decode_attention_block16(compile_for_chip, rows):
    """124M geometry over a block-16 pool: 16 slots x 64 blocks (1024
    tokens) + the reserved block; ``rows=4`` is the speculative verify
    chunk."""
    pool = ((16 * 64 + 1, 12, 16, 64), BF16)
    hlo = compile_for_chip(
        functools.partial(paged_decode_attention, impl="paged"),
        ((16, rows, 12, 64), BF16), pool, pool, ((16, 64), I32), ((16,), I32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("blocks", [(3,), (0, 1, 2)],
                         ids=["one_block", "three_blocks"])
def test_attention_kernel_keeps_block_name_on_mesh(topo, monkeypatch, blocks):
    """On a ``data=4`` mesh the kernel runs inside a ``shard_map``, and XLA
    names a call after its innermost scope: with the block's ``name=`` the
    calls are ``h_3.<k>`` — what a trace reader looks for
    (``benchmarks/families`` ``ATTENTION_OPS``) — and not ``shard_map.<k>``.
    Several blocks share one traced ``pallas_call`` a direction
    (``vmem_attention._traced_once``) and JAX lowers it once, yet each
    block's two kernels keep that block's name."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpudist.mesh import DATA_AXIS, create_mesh
    from tpudist.ops.attention import multi_head_attention

    monkeypatch.setattr(backend, "interpret", lambda: False)
    mesh = create_mesh(devices=topo.devices)
    rows = jax.ShapeDtypeStruct(
        (32, 1024, 16, 64), BF16, sharding=NamedSharding(mesh, P(DATA_AXIS)))

    def stack(x):
        for n in blocks:
            with jax.named_scope(f"h_{n}"):
                x = x + multi_head_attention(
                    x, x, x, causal=True, impl="vmem", mesh=mesh,
                    name=f"h_{n}")
        return x.astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(stack)).lower(rows).compile().as_text()
    kernels = re.findall(
        r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert len(kernels) == 2 * len(blocks), kernels  # forward and backward
    for n in blocks:
        named = [k for k in kernels if re.match(rf"^h_{n}(\.\d+)?$", k)]
        assert len(named) == 2, kernels


def test_zero1_fused_adamw_updates_a_sharded_leaf_in_shards(topo):
    """``shard_state(fused_adamw)`` on a ``data=4`` mesh in the cells' regime
    (bf16 gradients, f32 moments and masters, the bf16 copy, the clip,
    ``apply_updates``' add; state and masters donated, everything
    replicated but the state). A leaf stored sharded on a dimension the
    axis divides is ONE fusion whose results (copy, moments, new master)
    have the SHARD's shape; no moment is gathered or reduced and nothing is
    reshaped, padded or transposed; after it comes the one all-gather
    ZeRO-1 always pays, of the new master. What the compiler does add is
    whole-leaf copies beside that all-gather: of the donated master before
    the fusion, of the gathered one into the result. A compile for a
    described mesh says what the program holds, not what it costs: no cell
    runs ZeRO-1 (PERF.md §7)."""
    import math
    import re

    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpudist.mesh import DATA_AXIS, create_mesh
    from tpudist.optim import decay_mask, fused_adamw, shard_state

    mesh = create_mesh(devices=topo.devices)
    world = mesh.shape[DATA_AXIS]
    shapes = {"wte": (50257, 1024), "qkv": (1024, 3, 16, 64),
              "out": (16, 64, 1024), "bias": (4096,)}
    tx = shard_state(
        fused_adamw(3e-4, weight_decay=0.1, mask=decay_mask, clip_norm=1.0,
                    compute_dtype=BF16),
        mesh,
    )
    everywhere = NamedSharding(mesh, P())
    params, grads = (
        {k: jax.ShapeDtypeStruct(s, dtype, sharding=everywhere)
         for k, s in shapes.items()}
        for dtype in (F32, BF16)
    )
    stored = tx.state_shardings(params)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(tx.init, params), stored,
    )
    # every leaf here keeps its shape and is split over the axis
    assert all(m.shape == shapes[k] and DATA_AXIS in m.sharding.spec
               for k, m in state.mu.items())

    def update(g, s, p):
        u, s2 = tx.update(g, s, p)
        return optax.apply_updates(p, u), s2

    hlo = jax.jit(
        update, donate_argnums=(1, 2),
        out_shardings=(jax.tree.map(lambda _: everywhere, params), stored),
    ).lower(grads, state, params).compile().as_text()
    entry = hlo[hlo.index("ENTRY"):]
    # (name, result type, op, operands) of every instruction
    instrs = re.findall(
        r"^\s+(?:ROOT )?(\S+) = (.*?) ([\w\-]+)\((.*?)\)[,\n]", entry, re.M)

    def size(typ):  # elements of the largest array in a result type
        return max((math.prod(map(int, dims.split(",")))
                    for dims in re.findall(r"\[([\d,]+)\]", typ)), default=0)

    leaf = {math.prod(s) for s in shapes.values()}
    shard = {n // world for n in leaf}
    made_by = {name.lstrip("%"): op for name, _, op, _ in instrs}

    updates = [typ for _, typ, op, _ in instrs
               if op == "fusion" and typ.count("[") == 4]
    assert sorted(map(size, updates)) == sorted(shard), updates
    # no fusion yields a whole leaf: the update runs on shards alone
    assert not [typ for _, typ, op, _ in instrs
                if op == "fusion" and size(typ) in leaf]
    gathers = [typ for _, typ, op, _ in instrs if op == "all-gather"]
    assert sorted(map(size, gathers)) == sorted(leaf), gathers
    assert all(typ.startswith("f32") for typ in gathers), gathers
    ops = {op for _, typ, op, _ in instrs if size(typ) in leaf | shard}
    assert not {"all-reduce", "all-to-all", "collective-permute", "reshape",
                "transpose", "pad", "dynamic-update-slice"} & ops, ops
    copied = [made_by[args.split(",")[0].split()[-1].lstrip("%")]
              for _, typ, op, args in instrs
              if op == "copy" and size(typ) in leaf]
    assert set(copied) <= {"parameter", "all-gather"}, copied
    assert len(copied) <= 2 * len(shapes), copied


def test_zaya_cell_step_compiles_and_routes_without_one_hot_products(
        topo, monkeypatch):
    """The ``zaya1_8b_train_s4096`` cell's whole train step — its own
    configuration, traffic and family file, 494.8M parameters, 16,384
    tokens — compiled for one described chip: it fits, the attention kernel
    is there under the name the trace reader looks for (``cca_attn.<k>``),
    the grouped products are the compiler's ragged-dot kernels, and no
    matrix product on the token path (a 2048-wide operand) takes a
    ``[tokens, experts]`` one-hot operand — only the router's own last
    layer is that shape."""
    import json
    import os
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.families import common, zaya as family
    from tpudist import mesh as mesh_lib
    from tpudist.train import TrainState, make_train_step

    monkeypatch.setattr(backend, "interpret", lambda: False)
    # the family resolves ``attn auto`` for this process's CPU: answer for
    # the chip, as ``compile_for_chip`` does for the kernels
    monkeypatch.setattr(common, "resolve_attn", lambda requested, seq: "flash")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks/configs/zaya1-8b.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmarks/traffic/train_s4096_b4.json")) as f:
        traffic = json.load(f)
    mesh = mesh_lib.create_mesh(devices=topo.devices[:1])
    built = family.build(config, traffic, mesh)
    everywhere = NamedSharding(mesh, P())
    placed = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=everywhere),
        tree)
    params = placed(built["param_shapes"])
    state = TrainState(
        step=jax.ShapeDtypeStruct((), I32, sharding=everywhere),
        params=params, batch_stats={},
        opt_state=placed(jax.eval_shape(built["tx"].init, params)))
    kw = built["fit"]
    step = make_train_step(
        built["model"], built["tx"], mesh, loss_fn=kw["loss_fn"],
        input_key="tokens", label_key="tokens",
        forward_loss=kw["forward_loss"], fused=kw["fused"])
    tokens = traffic["per_chip_batch"] * traffic["seq_len"]
    batch = {"tokens": jax.ShapeDtypeStruct(
        (traffic["per_chip_batch"], traffic["seq_len"]), I32,
        sharding=everywhere)}
    traced = step.jitted.trace(state, batch)
    # the static counter: ``dots_saveable`` keeps the kernel's ``o`` and
    # ``lse``, so no block's backward launches the forward kernel again
    assert remat.forward_attention_kernels(traced.jaxpr) \
        == config["num_hidden_layers"]
    # half the experts are held: the sorted rows are ONE chunk, and the
    # expert layer brings no loop and no branch into the step
    assert ep.row_chunks(tokens, config["num_experts_held"],
                         config["num_experts"]) == (tokens, 1)
    assert not _expert_control_flow(traced.jaxpr)
    compiled = traced.lower().compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 4e9 < held < 12.5e9, held  # + the harness's 2 GB copy <= 14.5 GB
    hlo = compiled.as_text()
    kernels = re.findall(
        r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    attention = [k for k in kernels if re.match(family.ATTENTION_OPS, k)]
    # per layer: forward, dkv, dq
    assert len(attention) == 3 * config["num_hidden_layers"], kernels
    # equal widths of 128 reach the kernels as they did before the kernel
    # took a key width and a value width: every head-wide operand and
    # result of every attention kernel is [4, 8, 4096, 128], nothing padded
    for line in hlo.splitlines():
        if re.search(r"%?cca_attn[\w.]* = ", line) and "tpu_custom_call" in line:
            heads = re.findall(r"bf16\[(\d+),(\d+),(\d+),(\d+)\]", line)
            assert heads and set(heads) == {("4", "8", "4096", "128")}, line
    assert sum(k.startswith("ragged-dot") for k in kernels) >= \
        9 * config["num_hidden_layers"]
    experts = {config["num_experts"], config["num_experts_held"]}
    wide = config["hidden_size"]
    for line in hlo.splitlines():
        if " dot(" not in line and " convolution(" not in line:
            continue
        shapes = [tuple(int(n) for n in dims.split(","))
                  for dims in re.findall(r"\[([\d,]+)\]", line)]
        one_hot = any(tokens in s and experts & set(s) for s in shapes)
        token_path = any(tokens in s and wide in s for s in shapes)
        assert not (one_hot and token_path), line


def test_kanana_cell_step_compiles_at_the_published_widths(topo, monkeypatch):
    """The ``kanana2_30b_train_s8192`` cell's whole train step — its own
    configuration, traffic and family file, 576.0M parameters, 8,192
    tokens — compiled for one described chip: it fits beside the harness's
    copy, the MLA kernel is there under the name the trace reader looks
    for (``mla_attn.<k>``) with keys of 192 and values of 128 and nothing
    padded to 256, and the grouped products are the compiler's ragged-dot
    kernels over the 49,152 (token, choice) rows of a layer."""
    import json
    import os
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.families import common, kanana as family
    from tpudist import mesh as mesh_lib
    from tpudist.train import TrainState, make_train_step

    monkeypatch.setattr(backend, "interpret", lambda: False)
    monkeypatch.setattr(common, "resolve_attn", lambda requested, seq: "flash")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks/configs/kanana-2-30b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmarks/traffic/train_s8192_b1.json")) as f:
        traffic = json.load(f)
    mesh = mesh_lib.create_mesh(devices=topo.devices[:1])
    built = family.build(config, traffic, mesh)
    everywhere = NamedSharding(mesh, P())
    placed = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=everywhere),
        tree)
    params = placed(built["param_shapes"])
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == 575_955_456
    state = TrainState(
        step=jax.ShapeDtypeStruct((), I32, sharding=everywhere),
        params=params, batch_stats={},
        opt_state=placed(jax.eval_shape(built["tx"].init, params)))
    kw = built["fit"]
    step = make_train_step(
        built["model"], built["tx"], mesh, loss_fn=kw["loss_fn"],
        input_key="tokens", label_key="tokens",
        forward_loss=kw["forward_loss"], fused=kw["fused"])
    batch = {"tokens": jax.ShapeDtypeStruct(
        (traffic["per_chip_batch"], traffic["seq_len"]), I32,
        sharding=everywhere)}
    traced = step.jitted.trace(state, batch)
    assert remat.forward_attention_kernels(traced.jaxpr) \
        == config["num_hidden_layers"]
    rows = traffic["seq_len"] * config["num_experts_per_tok"]
    chunk_rows, n_chunks = ep.row_chunks(
        rows, config["num_experts_held"], config["n_routed_experts"])
    assert (chunk_rows, n_chunks) == (12_288, 4)
    # the loops over the live chunks sit INSIDE the stages' scopes, so the
    # benchmark's reader still finds every op of the layer under its stage
    assert _expert_control_flow(traced.jaxpr)
    lowered = traced.lower()
    assert set(_expert_stages(lowered)) == {
        "moe_norm", "moe_router", "moe_dispatch", "moe_experts",
        "moe_combine", "moe_shared"}
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # + the harness's 2.3 GB copy <= 15.1 GB of the chip's 16.9. The bytes
    # LIVE at the schedule's peak fell with the chunked expert layer (11.47
    # -> 11.22 GB); the allocation rose 12.27 -> 12.67 GB, because the
    # chunks that do not run still exist, zero-filled, in every buffer that
    # crosses a stage (three quarters of each)
    assert 8e9 < held < 12.8e9, held
    hlo = compiled.as_text()
    kernels = {
        name: line for line in hlo.splitlines()
        for name in re.findall(
            r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
            line)}
    attention = [k for k in kernels if re.match(family.ATTENTION_OPS, k)]
    # per layer: forward, dkv, dq (the kept ``o`` and ``lse`` spare the
    # backward a second forward launch)
    assert len(attention) == 3 * config["num_hidden_layers"], sorted(kernels)
    widths = {int(w) for k in attention
              for w in re.findall(r"bf16\[1,32,8192,(\d+)\]", kernels[k])}
    assert widths == {128, 192}, widths
    expert_layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    grouped = [k for k in kernels if k.startswith("ragged-dot")]
    assert len(grouped) >= 9 * expert_layers, len(grouped)
    # forward, recomputed forward and the backward's row-wide results
    # (the grouped products' outputs are not among the kept names), over
    # ONE chunk of the 49,152 rows each; none runs over all of them
    assert sum(f"[{chunk_rows}," in kernels[k] for k in grouped) \
        >= 6 * expert_layers
    assert not any(f"[{rows}," in kernels[k] for k in grouped)


def test_sdar_cell_step_compiles_at_the_published_widths(topo, monkeypatch):
    """The ``sdar_30b_bd_train_s4096`` cell's whole train step — its own
    configuration, traffic and family file, 551.0M parameters, 8,192 rows
    (the noised and the clean copy of 4,096 tokens) — compiled for one
    described chip: it fits beside the harness's copy, the masked flash
    kernel is there under the name the trace reader looks for
    (``bd_attn.<k>``), 5 forward launches and 3 kernels a layer, over 32
    heads of 128 at 8,192 rows, and the grouped products are the compiler's
    ragged-dot kernels over the 65,536 (row, choice) pairs of a layer."""
    import json
    import os
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.families import common, sdar as family
    from tpudist import mesh as mesh_lib
    from tpudist.train import TrainState, make_train_step

    monkeypatch.setattr(backend, "interpret", lambda: False)
    monkeypatch.setattr(common, "resolve_attn", lambda requested, seq: "flash")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks/configs/sdar-30b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            root, "benchmarks/traffic/bd_train_s4096_b1.json")) as f:
        traffic = json.load(f)
    mesh = mesh_lib.create_mesh(devices=topo.devices[:1])
    built = family.build(config, traffic, mesh)
    everywhere = NamedSharding(mesh, P())
    placed = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=everywhere),
        tree)
    params = placed(built["param_shapes"])
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == 550_984_960
    state = TrainState(
        step=jax.ShapeDtypeStruct((), I32, sharding=everywhere),
        params=params, batch_stats={},
        opt_state=placed(jax.eval_shape(built["tx"].init, params)))
    kw = built["fit"]
    step = make_train_step(
        built["model"], built["tx"], mesh, input_key="tokens",
        label_key="clean", forward_loss=kw["forward_loss"], fused=kw["fused"])
    shape = (traffic["per_chip_batch"], traffic["seq_len"])
    batch = {name: jax.ShapeDtypeStruct(shape, dtype, sharding=everywhere)
             for name, dtype in (("tokens", I32), ("clean", I32),
                                 ("loss_weight", F32))}
    traced = step.jitted.trace(state, batch)
    assert remat.forward_attention_kernels(traced.jaxpr) \
        == config["num_hidden_layers"] == 5
    pairs = 2 * traffic["seq_len"] * config["num_experts_per_tok"]
    chunk_rows, n_chunks = ep.row_chunks(
        pairs, config["num_experts_held"], config["num_experts"])
    assert (chunk_rows, n_chunks) == (16_384, 4)
    assert _expert_control_flow(traced.jaxpr)
    lowered = traced.lower()
    assert set(_expert_stages(lowered)) == {
        "moe_norm", "moe_router", "moe_dispatch", "moe_experts",
        "moe_combine"}
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 8e9 < held < 12.5e9, held  # + the harness's 2.2 GB copy <= 14.7 GB
    hlo = compiled.as_text()
    kernels = {
        name: line for line in hlo.splitlines()
        for name in re.findall(
            r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
            line)}
    attention = [k for k in kernels if re.match(family.ATTENTION_OPS, k)]
    # per layer: forward, dkv, dq (the kept ``o`` and ``lse`` spare the
    # backward a second forward launch)
    assert len(attention) == 3 * config["num_hidden_layers"], sorted(kernels)
    rows = 2 * traffic["seq_len"]
    for k in attention:
        heads = set(re.findall(r"bf16\[1,(\d+),(\d+),(\d+)\]", kernels[k]))
        assert heads == {("32", str(rows), "128")}, kernels[k][:300]
    grouped = [k for k in kernels if k.startswith("ragged-dot")]
    assert len(grouped) >= 9 * config["num_hidden_layers"], len(grouped)
    assert sum(f"[{chunk_rows}," in kernels[k] for k in grouped) \
        >= 6 * config["num_hidden_layers"]
    assert not any(f"[{pairs}," in kernels[k] for k in grouped)


def test_laguna_cell_step_compiles_at_the_published_widths(topo, monkeypatch):
    """The ``laguna_xs2_train_s8192`` cell's whole train step — its own
    configuration, traffic and family file, 565.2M parameters, 8,192
    tokens — compiled for one described chip: it fits beside the harness's
    copy; each kind's attention kernel is there under the name the trace
    reader looks for, three a layer — ``full_attn.<k>`` at 48 heads over
    the square grid, ``swa_attn.<k>`` at 64 heads over the band's grid —;
    and the grouped products are the compiler's ragged-dot kernels over
    one chunk of the 65,536 (token, choice) rows of a layer."""
    import json
    import os
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.families import common, laguna as family
    from tpudist import mesh as mesh_lib
    from tpudist.train import TrainState, make_train_step

    monkeypatch.setattr(backend, "interpret", lambda: False)
    monkeypatch.setattr(common, "resolve_attn", lambda requested, seq: "flash")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks/configs/laguna-xs-2.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmarks/traffic/train_s8192_b1.json")) as f:
        traffic = json.load(f)
    mesh = mesh_lib.create_mesh(devices=topo.devices[:1])
    built = family.build(config, traffic, mesh)
    everywhere = NamedSharding(mesh, P())
    placed = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=everywhere),
        tree)
    params = placed(built["param_shapes"])
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == 565_204_992
    state = TrainState(
        step=jax.ShapeDtypeStruct((), I32, sharding=everywhere),
        params=params, batch_stats={},
        opt_state=placed(jax.eval_shape(built["tx"].init, params)))
    kw = built["fit"]
    step = make_train_step(
        built["model"], built["tx"], mesh, loss_fn=kw["loss_fn"],
        input_key="tokens", label_key="tokens",
        forward_loss=kw["forward_loss"], fused=kw["fused"])
    batch = {"tokens": jax.ShapeDtypeStruct(
        (traffic["per_chip_batch"], traffic["seq_len"]), I32,
        sharding=everywhere)}
    traced = step.jitted.trace(state, batch)
    assert remat.forward_attention_kernels(traced.jaxpr) \
        == config["num_hidden_layers"]
    rows = traffic["seq_len"] * config["num_experts_per_tok"]
    chunk_rows, n_chunks = ep.row_chunks(
        rows, config["num_experts_held"], config["num_experts"])
    lowered = traced.lower()
    assert set(_expert_stages(lowered)) == {
        "moe_norm", "moe_router", "moe_dispatch", "moe_experts",
        "moe_combine", "moe_shared"}
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # 7.37 + 5.23 GiB under dots_saveable (3.03 GiB of temporaries under
    # full), + the harness's 2.26 GB copy <= 15.9 GB of the chip's 16.9
    assert 8e9 < held < 13.7e9, held
    hlo = compiled.as_text()
    kernels = {
        name: line for line in hlo.splitlines()
        for name in re.findall(
            r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
            line)}
    for kind, heads in (("full_attention", 48), ("sliding_attention", 64)):
        pattern = family.KINDS[kind].ATTENTION_OPS
        attention = [k for k in kernels if re.match(pattern, k)]
        layers = config["layer_types"][:config["num_hidden_layers"]].count(kind)
        # per layer: forward, dkv, dq
        assert len(attention) == 3 * layers, (kind, sorted(kernels))
        for k in attention:
            assert set(re.findall(r"bf16\[1,(\d+),8192,128\]", kernels[k])) \
                == {str(heads)}, kernels[k][:300]
    expert_layers = config["num_hidden_layers"] - 1
    grouped = [k for k in kernels if k.startswith("ragged-dot")]
    assert len(grouped) >= 9 * expert_layers, len(grouped)
    assert sum(f"[{chunk_rows}," in kernels[k] for k in grouped) \
        >= 6 * expert_layers
    assert not any(f"[{rows}," in kernels[k] for k in grouped)


def test_ssd_scan_fwd_bwd_compiles_at_a_mamba2_layers_shape(compile_for_chip):
    """The chunked state-space scan at Nemotron-3-Nano's Mamba-2 layer and
    the cell's sequence (64 heads of 64, 8 groups of state 128, 8,192
    positions in 64 chunks of 128), forward and backward as one program:
    two kernels, each once — the forward writing ``y`` and every chunk's
    float32 starting state in the kernel's tiles (two heads of 64 a
    128-lane tile), the backward (under ``transpose``) writing ``dx``."""
    from tpudist.ops.ssd import ssd_scan

    def loss(*args):
        return jnp.sum(ssd_scan(*args).astype(jnp.float32))

    hlo = compile_for_chip(
        jax.grad(loss, argnums=range(6)),
        ((1, 8192, 64, 64), jnp.bfloat16), ((1, 8192, 64), jnp.float32),
        ((64,), jnp.float32), ((1, 8192, 8, 128), jnp.bfloat16),
        ((1, 8192, 8, 128), jnp.bfloat16), ((64,), jnp.float32))
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    backward = [c for c in calls if "/transpose(" in c]
    assert len(backward) == 1
    (forward,) = [c for c in calls if c not in backward]
    head = forward.split(" custom-call(")[0]
    assert "bf16[1,8192,4096]" in head and "f32[1,64,8,4,128,128]" in head, \
        head
    head = backward[0].split(" custom-call(")[0]
    assert "(bf16[1,8192,4096]" in head, head


def test_nemotron_h_cell_step_compiles_at_the_published_widths(topo,
                                                               monkeypatch):
    """The ``nemotron3_nano_train_s8192`` cell's whole train step — its own
    configuration, traffic and family file, 528.1M parameters, 8,192
    tokens — compiled for one described chip: it fits beside the harness's
    copy under per-block ``dots_saveable``; the scan's forward kernel runs
    once a Mamba-2 layer (its output and states kept, not made again), its
    backward kernel once a layer too, and the trace reader finds all of
    them (by the kernel's name or its scope); the attention kernel is there
    under ``gqa_attn``, three calls; the expert layers' grouped products
    are the compiler's ragged-dot kernels over one chunk of the 49,152
    (token, choice) rows, on tiles at least 256 wide on both widths."""
    import json
    import os
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.families import common, nemotron_h as family
    from benchmarks.layer_metrics import ssd_roofline
    from benchmarks.layer_metrics.moe_ms import stage_of
    from tpudist import mesh as mesh_lib
    from tpudist.train import TrainState, make_train_step

    monkeypatch.setattr(backend, "interpret", lambda: False)
    monkeypatch.setattr(common, "resolve_attn", lambda requested, seq: "flash")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks/configs/nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmarks/traffic/train_s8192_b1.json")) as f:
        traffic = json.load(f)
    mesh = mesh_lib.create_mesh(devices=topo.devices[:1])
    built = family.build(config, traffic, mesh)
    everywhere = NamedSharding(mesh, P())
    placed = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=everywhere),
        tree)
    params = placed(built["param_shapes"])
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == 528_092_736
    state = TrainState(
        step=jax.ShapeDtypeStruct((), I32, sharding=everywhere),
        params=params, batch_stats={},
        opt_state=placed(jax.eval_shape(built["tx"].init, params)))
    kw = built["fit"]
    step = make_train_step(
        built["model"], built["tx"], mesh, loss_fn=kw["loss_fn"],
        input_key="tokens", label_key="tokens",
        forward_loss=kw["forward_loss"], fused=kw["fused"])
    batch = {"tokens": jax.ShapeDtypeStruct(
        (traffic["per_chip_batch"], traffic["seq_len"]), I32,
        sharding=everywhere)}
    traced = step.jitted.trace(state, batch)
    kinds = family.kinds(config)
    assert remat.forward_kernels(traced.jaxpr, ("ssd.py",)) == kinds.count("M")
    assert remat.pallas_kernels(traced.jaxpr, ("ssd.py",), "_bwd_kernel") \
        == kinds.count("M")
    assert remat.forward_attention_kernels(traced.jaxpr) == kinds.count("*")
    compiled = traced.lower().compile()
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # 7.39 + 4.00 GB under dots_saveable (2.96 GB of temporaries under
    # full), + the harness's 2.11 GB copy <= 13.5 GB of the chip's 17.2
    assert 8e9 < held < 12.5e9, held
    kernels = {
        name: line for line in compiled.as_text().splitlines()
        for name in re.findall(
            r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
            line)}
    op_name = lambda line: (re.findall(r'op_name="([^"]*)"', line)
                            or [""])[0]
    # the kernels under the Mamba-2 block's ``ssd_scan`` scope
    scans = [k for k in kernels if "/ssd_scan/" in op_name(kernels[k])]
    backward = [k for k in scans if "/transpose(" in op_name(kernels[k])]
    assert len(scans) == 2 * kinds.count("M"), sorted(kernels)
    assert len(backward) == kinds.count("M"), scans
    assert all(ssd_roofline.KERNEL.match(k) or stage_of(
        op_name(kernels[k]), k) == ssd_roofline.STAGE for k in scans), scans
    attention = [k for k in kernels if re.match(r"^gqa_attn(\.\d+)?$", k)]
    assert len(attention) == 3 * kinds.count("*"), sorted(kernels)
    rows = traffic["seq_len"] * config["num_experts_per_tok"]
    chunk_rows, n_chunks = ep.row_chunks(
        rows, config["num_experts_held"], config["n_routed_experts"])
    assert (chunk_rows, n_chunks) == (6144, 8)
    grouped = [k for k in kernels if k.startswith("ragged-dot")]
    assert len(grouped) >= 6 * kinds.count("E"), len(grouped)
    assert sum(f"[{chunk_rows}," in kernels[k] for k in grouped) \
        >= 4 * kinds.count("E")
    assert not any(f"[{rows}," in kernels[k] for k in grouped)
    # the products run at ``grouped_widths`` (2,816 x 2,048 for 2,688 x
    # 1,856, 1.156x the work): the compiler's kernel tiles both widths 256
    # or 512 wide, where it cut them 128 wide at the published widths
    d, ff = config["hidden_size"], config["moe_intermediate_size"]
    d_pad, ff_pad = ep.grouped_widths(d, ff)
    assert d_pad * ff_pad / (d * ff) == pytest.approx(1.156, abs=1e-3)
    tilings = [t for k in grouped if (t := re.search(
        r'ragged_dot_tiling="(\d+),(\d+),(\d+)"', kernels[k]))]
    assert len(tilings) >= 6 * kinds.count("E"), grouped
    assert all(min(int(t[2]), int(t[3])) >= 256 for t in tilings), \
        sorted({t[0] for t in tilings})
