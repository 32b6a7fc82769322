"""The cells' own programs, compiled by the TPU's own compiler for a
described (not attached) v5e, at the shapes the chip runs them.

The CPU tier proves arithmetic and hides everything the chip's compiler
decides: layouts, relayout loops, which table it holds in fast memory,
what Mosaic refuses of JAX's kernels. A compile that passes here is a
compile, not a run — the benchmark's cells and ``tests_tpu/`` are the runs.

libtpu admits one process at a time, and xdist workers each import every
test file: the topology is therefore described inside a module-scoped
fixture (never at import, in a ``skipif`` or in ``parametrize``), compiles
happen in the test's own process, and these tests stay in this ONE file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


class _Tpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


@pytest.fixture
def as_tpu_program(monkeypatch):
    """The dispatchers read ``jax.devices()[0].platform`` at trace time;
    here the attached backend is the CPU and the target is the chip."""
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Tpu()])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _struct(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def test_gather_attention_backward_has_no_relayout(topo):
    """``gat-fleet50k.train``'s attention at the cell's own shapes, under
    the one-device mesh the trainer sets. Until PR 25 the attention
    cotangent came out heads-major and the compiler re-laid it for the
    inverse-index gather in a 256-iteration ``while`` (147 ms of a 443 ms
    step, a third of it, under no scope a trace could name); until PR 30
    that gather read 3.8M rows out of the ``[N·K, 2·hidden]`` cotangent,
    1.6 GB in HBM at 13 ns a row (98 ms of a 180 ms step). The backward
    now sums dk and dv host by host out of a ``[q | dO | statistics]``
    table of N rows: no loop, no gather out of anything ``N·K`` rows
    long, the backward's table in the chip's fast memory (``S(1)``),
    and nothing that copies or reshapes a tensor of the gathered rows'
    size, forward or backward (the inverse index is 80 wide: whole
    16-row bfloat16 tiles). Plain XLA throughout: the program holds no
    hand-written kernel."""
    from dragonfly2_tpu.models.graph_transformer import (
        InverseIndex,
        gather_graph_attention,
    )
    from dragonfly2_tpu.parallel import data_parallel_mesh

    n, k, heads, hidden, inv_width = 50_000, 64, 4, 128, 80
    mesh = data_parallel_mesh(devices=topo.devices[:1])
    row, rep = _struct(mesh.shard_spec("data")), _struct(mesh.replicated)

    def loss(q, k_, v, nbr, val, inv):
        out = gather_graph_attention(q, k_, v, nbr, val, inv, heads=heads)
        return out.astype(jnp.float32).sum()

    with jax.set_mesh(mesh.mesh):
        compiled = _compile(
            jax.grad(loss, argnums=(0, 1, 2, 4)),
            row((n, hidden), jnp.bfloat16), rep((n, hidden), jnp.bfloat16),
            rep((n, hidden), jnp.bfloat16), row((n, k), jnp.int32),
            row((n, k), jnp.float32),
            InverseIndex(row((n, inv_width), jnp.int32),
                         row((n, inv_width), jnp.float32)))
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert " while(" not in text
    # Instructions of the entry computation run on their own; the same
    # words inside a fused computation are free.
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    shape_of = dict(re.findall(r"^\s*(%[\w.\-]+) = (\w+\[[\d,]*\]\S*) ",
                               entry, re.M))

    def rows(shape):
        return int(re.search(r"\[(\d+)", shape).group(1))

    # Row gathers as the v5e compiler emits them: (table, indices).
    gathers = re.findall(
        r"= (\w+\[[\d,]+\])\S* fusion\((%[\w.\-]+), (%[\w.\-]+)\), "
        r"kind=kCustom", entry)
    assert len(gathers) == 2, gathers
    (fwd_out, fwd_table, _), (bwd_out, bwd_table, _) = sorted(
        gathers, key=lambda g: rows(g[0]))
    assert rows(fwd_out) == n * k and rows(bwd_out) == n * inv_width
    for table in (fwd_table, bwd_table):
        assert rows(shape_of[table]) == n, shape_of[table]
        assert shape_of[table].endswith("S(1)}"), (table, shape_of[table])
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (?:copy|reshape)\(", entry)
        if math.prod(map(int, m.group(1).split(","))) >= n * k * hidden]
    assert not moved, moved
    # 3.36 GB (the forward's gathered rows 1.64, the backward's 3.07
    # after them); 4.20 GB with the [N, K, 2·hidden] cotangent until
    # PR 30, 6.55 with the loop and the forward's transposed copies
    # until PR 25.
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


def test_embedding_pass_at_model_load_leaves_the_chip_room(topo):
    """``GATParentScorer`` (and ``train_gat``'s eval pass) run the
    forward over the whole fleet under jit. Run op by op at the cell's
    50,000 rows it held 15.4 GB of the chip's 16 (PERF.md, PR 25: every
    ``[N, K, heads]`` float32 intermediate pads 4 lanes to 128 when
    nothing fuses it); compiled, its temporaries are what is held."""
    from dragonfly2_tpu.models.graph_transformer import GraphTransformer
    from dragonfly2_tpu.parallel import data_parallel_mesh

    n, k, feat = 50_000, 64, 8
    mesh = data_parallel_mesh(devices=topo.devices[:1])
    rep = _struct(mesh.replicated)
    model = GraphTransformer()
    shapes = (rep((n, feat), jnp.float32), rep((n, k), jnp.int32),
              rep((n, k), jnp.float32))
    params = jax.tree.map(
        lambda x: rep(x.shape, x.dtype),
        jax.eval_shape(model.init, jax.random.key(0), *shapes,
                       jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32)))

    def embed(p, feats, nbr, val):
        return model.apply(p, feats, nbr, val,
                           method=GraphTransformer.node_embeddings)

    compiled = _compile(embed, params, *shapes)
    assert "tpu_custom_call" not in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2.5e9     # 1.82 GB, PR 25


# The two sequence cells: one packed 8k sequence at the published
# widths. ``lfm2-24b-a2b-ep8.train``: hidden 2048, 32 query heads on 8
# key-value heads of 64, expert width 1536, 8 experts held of 64, top-4.
# ``laguna-xs2-ep32.train``: 48 (full layers) and 64 (sliding layers,
# window 512) query heads on 8 key-value heads of 128, expert width 512,
# 8 held of 256, top-8. ``keye-vl2-30b-a3b-ep16.train``: 32 query heads
# on 4 key-value heads of 128 over a selection of 2,048 keys on 32k
# sequences, expert width 768 (in parts of 8,192 tokens), 8 held of 128,
# top-8 under a softmax router.
SEQ, HIDDEN = 8192, 2048


@pytest.mark.parametrize("experts,width,top_k,scoring", [
    (64, 1536, 4, "sigmoid"), (256, 512, 8, "sigmoid"),
    (128, 768, 8, "softmax")],
    ids=["lfm2-24b-a2b-ep8", "laguna-xs2-ep32", "keye-vl2-30b-a3b-ep16"])
def test_expert_layer_is_grouped_products_for_the_chip(
        one_chip, as_tpu_program, experts, width, top_k, scoring):
    """The expert layer's forward and backward at a cell's widths: the
    three grouped products and their transposes are the megablox kernel
    (not one masked dense product per expert, and not XLA's own lowering
    of ``ragged_dot``, whose operations lose the ``df2.*`` scope), and no
    scatter of rows is left (rows move by gathers)."""
    from dragonfly2_tpu.parallel.moe import expert_layer

    s = _struct(one_chip)
    held = 8

    def loss(x, router, w1, w3, w2):
        out, _ = expert_layer(x, router, jnp.zeros(experts), w1, w3, w2,
                              (0, held), top_k=top_k, scoring=scoring)
        return out.sum()

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
        s((SEQ, HIDDEN), jnp.bfloat16), s((HIDDEN, experts), jnp.float32),
        s((held, HIDDEN, width), jnp.float32),
        s((held, HIDDEN, width), jnp.float32),
        s((held, width, HIDDEN), jnp.float32))
    text = compiled.as_text()
    # 3 products forward, 2 transposes each backward.
    assert text.count("tpu_custom_call") >= 9
    assert "ragged-dot" not in text
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert all("df2.moe.experts" in ln for ln in kernels)
    big = [ln for ln in text.splitlines()
           if " scatter(" in ln and f"{HIDDEN}]" in ln]
    assert not big, big[:2]


@pytest.mark.parametrize("heads,hd,window", [
    (32, 64, None), (48, 128, None), (64, 128, 512)],
    ids=["lfm2-24b-a2b-ep8", "laguna-xs2-ep32-full",
         "laguna-xs2-ep32-sliding"])
def test_sequence_attention_kernel_at_the_cells_shape(one_chip, heads, hd,
                                                      window):
    """Grouped-query causal attention over packed documents through the
    splash-attention kernel, forward and backward, at each cell's heads
    (groups of 4, 6 and 8 on 8 key-value heads), the sliding layers'
    with the window in the kernel's own mask."""
    from dragonfly2_tpu.models.seq_layers import kernel_attention

    s = _struct(one_chip)
    kv_heads = 8

    def loss(q, k, v, segments):
        return kernel_attention(q, k, v, segments, window).astype(
            jnp.float32).sum()

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        s((SEQ, heads, hd), jnp.bfloat16), s((SEQ, kv_heads, hd), jnp.bfloat16),
        s((SEQ, kv_heads, hd), jnp.bfloat16), s((SEQ,), jnp.int32))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    if window is None:
        # The full layers' tile tables (block_mask and data_next, the
        # kernels' first two operands) are computed from the segments:
        # behind any copy or bitcast stands a fusion, not a constant
        # baked into the program as the static causal mask's were.
        made_by = dict(re.findall(
            r"^\s*(%[\w.\-]+) = \S+ ([\w\-]+)\(", text, re.M))
        source = {name: arg for name, op, arg in re.findall(
            r"^\s*(%[\w.\-]+) = \S+ (copy|bitcast)\((%[\w.\-]+)\)",
            text, re.M)}
        calls = re.findall(
            r"= \([^\n]*? custom-call\((%[\w.\-]+), (%[\w.\-]+),[^\n]*"
            r"operand_layout_constraints=\{s8\[1,8,8\]\{2,1,0\}, "
            r"s8\[1,8,8\]", text)
        assert len(calls) == 2, calls
        for table in (t for call in calls for t in call):
            while table in source:
                table = source[table]
            assert made_by[table] == "fusion", (table, made_by[table])
    # No [heads, S, S] score matrix (32 x 8192 x 8192 float32 is 8.6
    # GB): 0.63, 0.86 and 2.30 GB, the sliding layers' the most because
    # the fused backward keeps a partial dq for each of its 16 key
    # blocks of 512 (8 of 1,024 in the other two).
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2e9 if window is None else 3e9)


def test_selected_attention_kernels_at_the_cells_shape(one_chip):
    """Attention over a computed selection at ``keye-vl2-30b-a3b-ep16``'s
    shape: one 32k sequence, 32 query heads on 4 key-value heads of 128,
    the selection as packed bits. Three kernels of the repo's own
    (forward, dq, dk and dv), which Mosaic takes at 1,024 x 1,024 tiles
    with the byte tile unpacked by shifts, a grid step taking all 8 query
    heads of a key-value head within the kernels' memory limit (Mosaic
    counts 32.25 MB for the forward's step and 40.55 MB for dq's): 4 x
    32 x 32 steps a call; nothing ``[S, S]`` wider than a bit a pair:
    the packed mask is 134 MB where the splash kernel's computed mask
    would be 4.3 GB of 32-bit words."""
    from dragonfly2_tpu.models.selected_attention import (
        grid_steps,
        heads_per_step,
        packed_attention,
    )

    s = _struct(one_chip)
    length, heads, kv_heads, hd = 32_768, 32, 4, 128
    assert heads_per_step(heads // kv_heads, 1024, hd, 2) == 8
    assert grid_steps(heads, kv_heads, length, hd, jnp.bfloat16,
                      1024) == 4 * 32 * 32

    def loss(q, k, v, packed):
        return packed_attention(q, k, v, packed, 1024).astype(
            jnp.float32).sum()

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        s((heads, length, hd), jnp.bfloat16),
        s((kv_heads, length, hd), jnp.bfloat16),
        s((kv_heads, length, hd), jnp.bfloat16),
        s((length, length // 8), jnp.uint8))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    # 1.5 GB: q, k, v, out and their cotangents, the log-sum-exp and
    # delta on 128 lanes each; a float32 [S, S] is 4.3 GB.
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def test_the_third_familys_step_fits_the_chip(topo, as_tpu_program):
    """``keye-vl2-30b-a3b-ep16.train``'s whole step (0.314B parameters,
    2 sequences of 32,768 positions, every published width) through
    ``seq_trainer.build_train_step``, as the benchmark's runner builds
    it: the chip's compiler refuses a program that does not fit its 16
    GB, and takes this one. Its kernels are the selection's three (a
    layer: forward, recomputed forward, dq, dkv) under
    ``df2.seq.attn_sparse`` and megablox under ``df2.moe.experts``; the
    selection is kept for the backward pass, not ranked again."""
    import json

    import optax

    from dragonfly2_tpu.models import keye_vl2
    from dragonfly2_tpu.parallel import data_parallel_mesh
    from dragonfly2_tpu.train import seq_trainer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "keye-vl2-30b-a3b-ep16.json")) as fh:
        spec = json.load(fh)
    held, published = spec["deployment"], spec["published"]
    cfg = keye_vl2.KeyeVL2Config.from_published(
        spec, num_experts=published["num_experts"],
        vocab_size=published["vocab_size"],
        num_hidden_layers=published["num_hidden_layers"],
        layers=tuple(held["layers_kept"]),
        experts_held=tuple(held["experts_held"]),
        vocab_held=tuple(held["vocab_rows_held"]))
    mesh = data_parallel_mesh(devices=topo.devices[:1])
    layers = len(cfg.kept_layers)

    def make_state():
        params = {}
        for path, shape, _ in keye_vl2.param_shapes(cfg):
            node = params
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = jnp.zeros(shape, jnp.float32)
        return seq_trainer.SeqTrainState.create(
            apply_fn=None, params=params,
            tx=optax.adamw(1e-4, weight_decay=0.1),
            router_bias=jnp.zeros((layers, cfg.num_experts)),
            routing_counts=jnp.zeros((layers, cfg.num_experts), jnp.uint32),
            sparse_counts=jnp.zeros((3, 3), jnp.uint32))

    rep = mesh.replicated
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        jax.eval_shape(make_state))
    rows, length = spec["corpus"]["tokens"] // spec["seq_len"], spec["seq_len"]

    def i32(*shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.int32,
            sharding=mesh.batch_sharding if len(shape) == 1 else rep)

    compiled = seq_trainer.build_train_step(cfg, mesh).lower(
        state, i32(rows, length), i32(rows, length), i32(spec["batch"]),
        i32(rows, length)).compile()
    memory = compiled.memory_analysis()
    # Parameters and Adam's two moments (12 bytes a parameter) and the
    # corpus's three arrays.
    assert 3.7e9 < memory.argument_size_in_bytes < 3.9e9
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    sparse = [ln for ln in calls if "df2.seq.attn_sparse" in ln]
    assert len(sparse) == 4 * layers and all(
        "df2.seq.attn_sparse" in ln or "df2.moe.experts" in ln
        for ln in calls)
    assert not any("ragged-dot" in ln for ln in calls)
    # Scored and ranked in the forward pass alone.
    assert not any("rematted_computation" in ln and (
        "df2.seq.index" in ln or "df2.seq.select" in ln)
        for ln in compiled.as_text().splitlines())
    _head_products_run_forward(compiled.as_text(), cfg.held_vocab[1])


def _head_products_run_forward(text: str, rows: int) -> None:
    """The loss head's products (those with the head's rows in their
    result: the logits and the rows' gradient) all run in the forward
    pass, where ``seq_layers.head_loss`` forms its gradients: none is
    made again in a recomputation or in the backward pass."""
    head = [ln for ln in text.splitlines()
            if " convolution(" in ln and ("df2.loss" in ln
                                         or "df2.seq.exit" in ln)
            and str(rows) in re.match(r"\s*\S+ = \w+\[([\d,]*)\]",
                                      ln).group(1).split(",")]
    assert head and not any("rematted_computation" in ln or "transpose("
                            in ln for ln in head), head


def test_the_looped_familys_step_fits_the_chip(topo, as_tpu_program):
    """``ouro-2.6b-pp12.train``'s whole step (0.407B parameters, 4
    sequences of 4,096 positions, every published width, all 49,152
    rows of the head) through ``seq_trainer.build_train_step``, as the
    benchmark's runner builds it: four layers traced once as one pass
    of a loop that runs four times, so the kernels appear once a layer
    in the forward loop and twice in the backward one (the recomputed
    forward and the fused backward); the exits' logits are made once,
    after the loop, a block of positions at a time, never held for all
    four exits, and the head's products run in the forward pass alone;
    no pass holds a copy of the weights of its own."""
    import json

    import optax

    from dragonfly2_tpu.models import ouro
    from dragonfly2_tpu.parallel import data_parallel_mesh
    from dragonfly2_tpu.train import seq_trainer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "ouro-2.6b-pp12.json")) as fh:
        spec = json.load(fh)
    held, published = spec["deployment"], spec["published"]
    cfg = ouro.OuroConfig.from_published(
        spec, vocab_size=published["vocab_size"],
        num_hidden_layers=published["num_hidden_layers"],
        layers=tuple(held["layers_kept"]),
        vocab_held=tuple(held["vocab_rows_held"]))
    mesh = data_parallel_mesh(devices=topo.devices[:1])

    def make_state():
        params = {}
        for path, shape, _ in ouro.param_shapes(cfg):
            node = params
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = jnp.zeros(shape, jnp.float32)
        return seq_trainer.SeqTrainState.create(
            apply_fn=None, params=params,
            tx=optax.adamw(1e-4, weight_decay=0.1),
            router_bias=jnp.zeros((0, 0)),
            routing_counts=jnp.zeros((0, 0), jnp.uint32),
            exit_mass=jnp.zeros((cfg.total_ut_steps, 3), jnp.uint32))

    rep = mesh.replicated
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        jax.eval_shape(make_state))
    rows, length = spec["corpus"]["tokens"] // spec["seq_len"], spec["seq_len"]

    def i32(*shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.int32,
            sharding=mesh.batch_sharding if len(shape) == 1 else rep)

    compiled = seq_trainer.build_train_step(cfg, mesh).lower(
        state, i32(rows, length), i32(rows, length), i32(spec["batch"]),
        i32(rows, length)).compile()
    memory = compiled.memory_analysis()
    # Parameters and Adam's two moments (12 bytes a parameter) and the
    # corpus's three arrays.
    assert 4.8e9 < memory.argument_size_in_bytes < 5.0e9
    # 7.2 GB by the compiler's count (it counts high: PERF.md).
    assert memory.temp_size_in_bytes < 10e9
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    assert len(calls) == 3 * len(cfg.kept_layers)
    assert "df2.seq.exit" in text
    passes = cfg.total_ut_steps
    for shape in (f"[{passes},{length},{cfg.held_vocab[1]}]",
                  f"[{passes},{cfg.hidden_size},{cfg.intermediate_size}]",
                  f"[{passes},{cfg.hidden_size},{cfg.hidden_size}]"):
        assert shape not in text, shape
    _head_products_run_forward(text, cfg.held_vocab[1])


def _runs_on_its_own(text: str) -> str:
    """The instructions of a compiled program that run on their own: the
    entry computation's and the loops' bodies' (the same words inside a
    fused computation are free)."""
    names = {m.group(1) for m in re.finditer(
        r"^ENTRY (%[\w.\-]+) ", text, re.M)}
    names |= set(re.findall(r"\bbody=(%[\w.\-]+)", text))
    return "\n".join(
        block for block in text.split("\n\n")
        if block.lstrip().removeprefix("ENTRY ").split(" ", 1)[0] in names)


@pytest.mark.parametrize("chips", [1, 4])
def test_fused_graphsage_step_at_the_cells_shapes(topo, chips):
    """``sage-fleet100k.train`` (and ``.dp4``: the same rows a chip): the
    whole fused step on the row tables (100,000 hosts, rows 256 lanes
    wide, 9,999,650 target edges, batch 131,072 a chip, fan-outs (10, 5))
    fits the chip with room, hop 2 runs in slices, and sampling adds no
    collective to the data-parallel step's two gradient all-reduces.

    **The layout** (PR 34): a hop's tensors go from the sampler's slices
    through the feature gather to the model's sums fan-outs leading and
    the batch trailing, so nothing lays hop 2's 13,107,200 feature rows
    (lane-padded: 3.4 GB) out again. Until then a re-tiling reshape and
    a copy of ``bf16[131072,2,10,5,8]``, a reshape of the ``s32[13107200]``
    ids, a transpose of every hop into batch order and a relayout of hop
    1's rows were 43.5 of the step's 133.4 ms (ledger, PR 33)."""
    import optax
    from flax.training import train_state

    from dragonfly2_tpu.models.graphsage import GraphSAGE
    from dragonfly2_tpu.parallel import data_parallel_mesh
    from dragonfly2_tpu.train import fused_sampling as fs

    hosts, records, batch, feat, width = 100_000, 9_999_650, 131_072, 8, 256
    f1, f2 = fanouts = (10, 5)
    mesh = data_parallel_mesh(devices=topo.devices[:chips])
    rep = _struct(mesh.replicated)
    model = GraphSAGE()

    def init(key):
        z = jnp.zeros
        params = model.init(
            key, z((2, 2, feat)), z((f1, 2, 2, feat)), z((f1, 2, 2)),
            z((f1, 2, 2)), z((f2, f1, 2, 2, feat)), z((f2, f1, 2, 2)),
            z((f2, f1, 2, 2)))
        return train_state.TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adamw(1e-3))

    state = jax.tree.map(lambda x: rep(x.shape, x.dtype),
                         jax.eval_shape(init, jax.random.key(0)))
    graph = fs.RowTables(rep((hosts, width), jnp.int32),
                         rep((hosts, width), jnp.int32),
                         rep((hosts, feat), jnp.float32))
    edges = fs.EdgeTables(rep((records,), jnp.int32),
                          rep((records,), jnp.int32),
                          rep((records,), jnp.float32))
    ids = _struct(mesh.batch_sharding)((batch * chips,), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = fs.make_fused_train_step(model, mesh, fanouts).lower(
        state, graph, edges, ids, rep(key.shape, key.dtype)).compile()
    memory = compiled.memory_analysis()
    # 0.33 + 4.79 GB, PR 34 (0.33 + 9.01 with the re-tiled copy of hop
    # 2's feature rows, PR 28; the CSR step: 0.21 + 8.82).
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 8e9
    text = compiled.as_text()
    assert "df2.sample.hop2)/while/body" in text
    assert text.count(f"s32[327680,{width}]") > 0      # a slice's rows
    for op in ("all-gather(", "collective-permute(", "all-to-all("):
        assert op not in text, op
    assert text.count("all-reduce(") == (2 if chips > 1 else 0)
    # Every row fetch reads its table out of the chip's fast memory
    # (layout suffix S(1)): the two tables are read one after the other,
    # so the compiler moves each in ahead of its fetch. Out of HBM a row
    # costs 12 ns, not 2.8 (PERF.md section 6, PR 28).
    fetches = re.findall(
        rf"= s32\[\d+,{width}\]\S* fusion\((%[\w.\-]+), [^)]*\), "
        r"kind=kCustom", text)
    assert len(fetches) == 4, fetches
    for table in fetches:
        (layout,) = re.findall(
            rf"^\s*{re.escape(table)} = (s32\[{hosts},{width}\]\S*) ",
            text, re.M)
        assert layout.endswith("S(1)}"), (table, layout)
    # The layout. Nothing is batch-major any more...
    assert not re.search(rf"\[{batch},2,{f1}(,{f2})?(,\d+)?\]", text)
    # ... and of the instructions that run on their own, those that move
    # a tensor without computing on it (a copy, a transpose, a reshape
    # that is no bitcast):
    slots = 2 * batch * f1 * f2
    moved = [(dtype, math.prod(map(int, dims.split(","))), line)
             for line, dtype, dims in re.findall(
                 r"^\s*((?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]+)\]\S* "
                 r"(?:copy|transpose|reshape)\()", _runs_on_its_own(text),
                 re.M)]
    # at most one over hop 2's feature rows (none today: the masked sum
    # reads the gather's rows as it wrote them),
    rows = [line for dtype, size, line in moved
            if dtype != "s32" and size >= slots * feat]
    assert len(rows) <= 1, rows
    # and of hop 2's ids between the sampler and the gather no flat
    # ``s32[13107200]`` is made again (4.2 ms until PR 34); what is left
    # moves whole tiles: the slices into their places in the batch and
    # the ``[.., 2, B]`` tiles into the flat gather's (0.2 ms each by the
    # compiler's estimate).
    ids_moved = [line for dtype, size, line in moved
                 if dtype == "s32" and size == slots]
    assert not any(f"s32[{slots}]" in line for line in ids_moved), ids_moved
    assert len(ids_moved) <= 2, ids_moved
