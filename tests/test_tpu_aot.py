"""Compile the TPU kernels with the chip's own toolchain — no chip.

libtpu ships the XLA:TPU and Mosaic compilers, and
``jax.experimental.topologies`` hands out a v5e topology to compile
against from a CPU-only box.  That goes one step past the tier-1
lowering tests (``TestLowersForTpu``): it catches what Mosaic itself
refuses — VMEM overflow, unsupported vector types, a DMA slice that is
not tile-aligned — before a chip call is spent on it.  It says nothing
about what the program does when it runs.

Slow-marked: loading libtpu into the test process is seconds of
start-up noise tier-1 does not need.  Run with
``pytest -m slow tests/test_tpu_aot.py``.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.ops import pallas_ops, ring  # noqa: E402

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    return topologies.get_topology_desc("v5e:2x2", "tpu").devices


@pytest.fixture(autouse=True)
def compiled_mode(monkeypatch):
    monkeypatch.delenv("HVTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)


def _compile(f, *args):
    return jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_ops_compile_at_resnet50_bucket_size(v5e_2x2, dtype):
    sh = NamedSharding(Mesh(np.array(v5e_2x2[:1]), ("world",)), P())
    n = 25_557_032

    def roundtrip(x, seed):
        q, s, _ = pallas_ops.quantize_int8_blocks(x)
        qs, _, _ = pallas_ops.quantize_int8_blocks(
            x, stochastic=True, seed=seed)
        return (qs, pallas_ops.dequantize_int8_blocks(q, s, n),
                pallas_ops.fused_scale_cast(x, 0.125, jnp.bfloat16))

    _compile(roundtrip,
             jax.ShapeDtypeStruct((n,), dtype, sharding=sh),
             jax.ShapeDtypeStruct((), jnp.int32, sharding=sh))


@pytest.mark.parametrize("n_dev", [2, 4])
@pytest.mark.parametrize("per_rank", [5, 3_000_001])
def test_ring_kernels_compile(v5e_2x2, n_dev, per_rank):
    mesh = Mesh(np.array(v5e_2x2[:n_dev]), ("world",))

    def wrap(body):
        return jax.shard_map(body, mesh=mesh, in_specs=(P("world"),),
                             out_specs=P(), check_vma=False)

    x = jax.ShapeDtypeStruct(
        (n_dev, per_rank), jnp.float32,
        sharding=NamedSharding(mesh, P("world")))
    for quantized in (False, True):
        _compile(wrap(lambda xs: ring.ring_allreduce(
            xs[0], axis_name="world", quantized=quantized)), x)
    block = jax.ShapeDtypeStruct(
        (n_dev * 512, 128), jnp.float32,
        sharding=NamedSharding(mesh, P("world")))
    _compile(wrap(lambda xs: ring.ring_allgather_2d(
        xs, axis_name="world")), block)


# -- the block-diffusion attention's kernels (ops/flash_attention.py) -------

ATTENTION_KERNELS = ("hvtpu_flash_attention_fwd", "hvtpu_flash_attention_dq",
                     "hvtpu_flash_attention_dkv")


def _attention_kernels_by_scope(text):
    """Which scope ``benchmark/scopes.py`` finds for each kernel of the
    compiled text: the join ``attention_ms_per_step`` and
    ``block_attention_roofline`` read the kernels' device time by."""
    from benchmark import scopes

    by_instruction = scopes.scope_by_instruction(text)
    return {kernel: {scope for name, scope in by_instruction.items()
                     if name.startswith(kernel)}
            for kernel in ATTENTION_KERNELS}


def test_attention_kernels_compile_at_the_cells_shape_and_keep_their_scope(
        v5e_2x2):
    """``sdar-30b-a3b-1of8-t8k-b2``'s attention: 2 sequences of 2 x
    8,192 positions, 4 query heads over 1 key/value head of 128, bf16,
    forward and gradient, at the block sizes the model states."""
    from horovod_tpu.models import block_diffusion as bd

    sh = NamedSharding(Mesh(np.array(v5e_2x2[:1]), ("world",)), P())
    q = jax.ShapeDtypeStruct((2, 16384, 4, 128), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((2, 16384, 1, 128), jnp.bfloat16, sharding=sh)

    def loss(q, k, v, target):
        out = bd.tiled_attention(q, k, v, block_length=4,
                                 tile=bd._ATTENTION_TILE)
        return jnp.sum((out * target).astype(jnp.float32))

    compiled = _compile(jax.grad(loss, (0, 1, 2)), q, kv, kv, q)
    assert _attention_kernels_by_scope(compiled.as_text()) == {
        kernel: {"hvtpu:attention"} for kernel in ATTENTION_KERNELS}


def _compiled_step(cell, v5e_2x2, config=None):
    """The whole step of a cell as ``benchmark/job.py`` builds it,
    compiled for one described chip."""
    import horovod_tpu as hvt
    from benchmark import cells, job

    config = config or cell.config
    workload = cells.load_builder(config).build(config)
    hvt.init()
    try:
        mesh = Mesh(np.array(v5e_2x2[:1]), ("world",))
        tx = hvt.DistributedOptimizer(
            job.make_optimizer(config["optimizer"]), axis_name="world",
            compression=getattr(hvt.Compression, cell.traffic["compression"]))

        def fresh(key):
            params, model_state = workload.init(key, None)
            return params, model_state, tx.init(params)

        def placed(tree, spec):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
                tree)

        batch = workload.make_pool(
            np.random.default_rng(0), cell.traffic["batch_per_chip"],
            cell.traffic["feed"]["dtype"])
        return job.make_step(mesh, workload.loss_fn, tx).trace(
            *placed(jax.eval_shape(fresh, jax.random.PRNGKey(0)), P()),
            placed(batch, P("world"))).lower(
                lowering_platforms=("tpu",)).compile()
    finally:
        hvt.shutdown()


def test_the_transformer_cells_step_fits_and_its_kernels_keep_their_scope(
        v5e_2x2):
    """The whole step of ``sdar-30b-a3b-1of8-t8k-b2`` as ``benchmark/job
    .py`` builds it, for one described chip: it needs no more memory
    than with attention in XLA tiles (12.80 GB: arguments, outputs and
    temporaries less what is aliased; PERF.md, findings of PR 28; 11.79
    with the experts' products in the grouped kernels, 10.09 in the
    tile loop before them: the allocator on the chip counts some 2 GB
    less, under the 12 GiB of ISSUE 37), every attention kernel in it,
    the recomputed forward too, is found under ``hvtpu:attention``, and
    the expert layer's products are the grouped kernels'."""
    from benchmark import cells

    cell = cells.load_cell("sdar-30b-a3b-1of8-t8k-b2")
    compiled = _compiled_step(cell, v5e_2x2)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) <= 12.80e9
    text = compiled.as_text()
    assert _attention_kernels_by_scope(text) == {
        kernel: {"hvtpu:attention"} for kernel in ATTENTION_KERNELS}
    _no_pass_over_a_whole_row_buffer(text, cell)
    _the_expert_layers_loops_lie_under_its_scopes(text)
    _the_experts_products_are_the_grouped_kernels(text)
    _the_guards_branch_runs_under_the_updates_scope(text)


def _the_experts_products_are_the_grouped_kernels(text, layers=1):
    """Under ``hvtpu:moe.experts`` the step holds the two kernels of
    ``ops/grouped_ffn.py`` once each for every expert layer that is a
    program of its own (the forward pass a layer's ``checkpoint`` makes
    again feeds nothing and is dropped) and no loop: nothing of that scope lies inside a loop of a layer, and no
    loop updates a slice of an ``f32[16, ., .]`` sum of weight
    gradients a tile at a time.  The kernels carry no kernel metadata,
    which XLA would print over several lines where ``benchmark/scopes
    .py`` cannot follow."""
    import re

    from benchmark import scopes

    by_instruction = scopes.scope_by_instruction(text)
    kernels = {name: scope for name, scope in by_instruction.items()
               if name.startswith("hvtpu_grouped_ffn")}
    assert sorted(re.sub(r"\.\d+$", "", name) for name in kernels) == (
        ["hvtpu_grouped_ffn_bwd"] * layers
        + ["hvtpu_grouped_ffn_fwd"] * layers)
    assert set(kernels.values()) == {"hvtpu:moe.experts"}
    for line in re.findall(r"^.*%hvtpu_grouped_ffn\S* = .*$", text,
                           re.MULTILINE):
        assert "kernel_metadata" not in line.replace(
            "kernel_metadata={}", "")
    assert [name for name, op_name in _in_loops(text, 2)
            if by_instruction.get(name) == "hvtpu:moe.experts"] == []
    for computation in re.split(r"\n(?=%\S+ \()", text):
        head = computation.split("\n", 1)[0]
        if "while/body" in computation and re.search(
                r"= f32\[16,\d+,\d+\]\S* dynamic-update-slice\(",
                computation):
            raise AssertionError(f"a loop writes into an f32[16, ., .]: "
                                 f"{head[:80]}")


def _the_guards_branch_runs_under_the_updates_scope(text):
    """``DistributedOptimizer``'s guard is one ``conditional`` named by
    ``hvtpu:optimizer.update``, and the branch that applies the update
    holds instructions of that scope (the momentum's multiply): the
    scope lies around the whole ``lax.cond``, not beside it.  On one
    chip the small leaves' bucket is what is left of the exchange."""
    import re

    guard = re.search(
        r"^\s*%\S+ = .* conditional\(.*branch_computations=\{"
        r"%(?P<skip>[^\s,]+), %(?P<apply>[^\s}]+)\}.*"
        r'op_name="[^"]*hvtpu:optimizer\.update/cond"', text, re.MULTILINE)
    assert guard, "no conditional under hvtpu:optimizer.update"
    start = text.index(f"\n%{guard['apply']} (")
    branch = text[start:text.index("\n}\n", start)]
    assert "hvtpu:optimizer.update/cond/branch_1_fun/mul" in branch
    assert "hvtpu:optimizer.guard" not in branch
    for scope in ("hvtpu:optimizer.guard", "hvtpu:exchange.pack",
                  "hvtpu:exchange.unpack"):
        assert scope in text, scope


def _no_pass_over_a_whole_row_buffer(text, cell, rows=None):
    """The expert layer's buffers in expert order (262,144 rows of
    2,048 in the transformer cell, of which an even routing uses an
    eighth; ``rows`` where a cell's are counted otherwise) are
    allocated, written a tile at a time by the gathers and in place by
    the grouped kernels: nothing fills one, and nothing copies one
    (which is what XLA does in every layer with an ``AllocateBuffer``
    it has moved out of the layers' scan, and with two rooms of one
    shape made from one operand)."""
    import re

    from horovod_tpu.parallel import moe

    rows = rows or moe.buffer_rows(
        cell.traffic["batch_per_chip"] * 2 * cell.traffic["sequence_length"],
        cell.config["num_experts_per_tok"], cell.config["num_experts"])
    made = {}
    for m in re.finditer(
            r"^\s*(?:ROOT\s+)?%(?P<name>\S+) = \w+\[" + str(rows)
            + r",\d+\]\S* (?P<op>[\w-]+)\((?P<rest>.*)$", text,
            re.MULTILINE):
        made.setdefault(m["op"], []).append(m)
    assert made, f"no array of {rows} rows in the step"
    assert set(made) <= {"custom-call", "fusion", "dynamic-update-slice",
                         "get-tuple-element", "parameter", "while"}, {
        op: [m["name"] for m in found] for op, found in made.items()}
    for m in made["custom-call"]:
        assert m["name"].startswith(("hvtpu_moe_row_buffer",
                                     "hvtpu_grouped_ffn")), m["name"]
    for m in made["fusion"]:            # a loop's write of one tile, in place
        body = re.search(r"calls=%([\w.-]+)", m["rest"])[1]
        start = text.index(f"\n%{body} ")
        assert "dynamic-update-slice(" in text[start:text.index(
            "\n}", start)], m["name"]


def _the_expert_layers_loops_lie_under_its_scopes(text):
    """On a TPU the attention runs in kernels, so every loop inside the
    layers' scan is the expert layer's row movement: each instruction
    of one, and the kernels that allocate its buffers, carries an
    ``hvtpu:moe.`` scope, so that ``moe_ms_per_step`` holds the layer's
    whole cost."""
    import re

    from benchmark import scopes

    by_instruction = scopes.scope_by_instruction(text)
    in_a_loop_of_a_layer = [
        (name, op_name) for name, op_name in re.findall(
            r'^\s*(?:ROOT\s+)?%(\S+) = .*?op_name="([^"]*)"', text,
            re.MULTILINE) if op_name.count("while/body") >= 2]
    # the gathers, the way back and the scalars' scatter: the products
    # run in no loop (500 and more with their tile loop)
    assert len(in_a_loop_of_a_layer) > 300
    assert [pair for pair in in_a_loop_of_a_layer
            if not by_instruction.get(pair[0], "").startswith("hvtpu:moe.")
            ] == []
    buffers = {scope for name, scope in by_instruction.items()
               if name.startswith("hvtpu_moe_row_buffer")}
    assert buffers and buffers <= {"hvtpu:moe.dispatch", "hvtpu:moe.combine"}


# -- the hybrid state-space cell (models/hybrid_ssm.py) ----------------------

HYBRID_CELL = "granite-4.0-h-micro-10of40-t8k-b2"
# what lax.scan and the layer's jax.checkpoint themselves add to a loop's
# body around the layer they run: the slices of the stacked parameters,
# the writes of their gradients, the copies of what is kept
SCAN_PLUMBING = ("dynamic_slice", "dynamic_update_slice", "squeeze",
                 "broadcast_in_dim", "add", "sub", "closed_call", "remat2")


def _in_loops(text, depth):
    """(instruction, op_name) of the compiled text that lie inside at
    least ``depth`` nested loops."""
    import re

    return [(name, op_name) for name, op_name in re.findall(
        r'^\s*(?:ROOT\s+)?%(\S+) = .*?op_name="([^"]*)"', text, re.MULTILINE)
        if op_name.count("while/body") >= depth]


def test_the_hybrid_cells_step_fits_one_chip(v5e_2x2):
    """The whole step of ``granite-4.0-h-micro-10of40-t8k-b2`` as
    ``benchmark/job.py`` builds it, 772 M parameters trained at 12 bytes
    each and 16,384 tokens a step, for one described chip.  By the
    figure the transformer cell's case uses (arguments, outputs and
    temporaries less what is aliased) it needs no more than with its
    attention in XLA tiles (15.60 GB; the allocator on the chip counts
    14.4: PERF.md, findings of PR 32).  Its attention runs in the three
    kernels of ``ops/flash_attention.py``, heads of 64 and document ids
    and all, each found under ``hvtpu:attention`` where
    ``document_attention_ms_per_step`` reads it, the recomputed forward
    too, and none with kernel metadata (``benchmark/scopes.py`` reads
    an instruction as one line).  What XLA still does under that scope
    makes no softmax (no ``reduce-window`` as wide as the keys: 47 ms a
    tile on the chip before PR 32's barrier) and moves no query or
    output into another layout (a head of 64 zero-filled to 128 lanes
    did: ten copies of 67 to 268 MB a pass), and the chunk scan's loops
    hold nothing outside ``hvtpu:ssm.scan``."""
    import re

    from benchmark import cells, scopes

    compiled = _compiled_step(cells.load_cell(HYBRID_CELL), v5e_2x2)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) <= 15.60e9
    text = compiled.as_text()
    assert _attention_kernels_by_scope(text) == {
        kernel: {"hvtpu:attention"} for kernel in ATTENTION_KERNELS}
    kernels = re.findall(
        r"^\s*(?:ROOT\s+)?%hvtpu_flash_attention_\w+(?:\.\d+)? = .*$", text,
        re.MULTILINE)
    assert len(kernels) == 4            # forward, recomputed, dq, dk/dv
    assert not any("kernel_metadata" in line.replace(
        "kernel_metadata={}", "") for line in kernels)
    windows = [max(int(n) for n in size.split("x")) for size in re.findall(
        r"reduce-window\([^\n]*window=\{size=([\dx]+)", text)]
    assert windows and max(windows) <= 256, sorted(set(windows))
    by_instruction = scopes.scope_by_instruction(text)
    relaid = [m[0] for m in re.finditer(
        r"^\s*%(?P<name>\S+) = bf16\[2,8192,\d+(?:,\d+)?\]\S* "
        r"(?:copy|pad|transpose)\(.*$", text, re.MULTILINE)
        if by_instruction.get(m["name"]) == "hvtpu:attention"]
    assert relaid == []
    in_a_chunk_loop = _in_loops(text, 2)
    assert len(in_a_chunk_loop) > 300
    assert {by_instruction.get(name) for name, _ in in_a_chunk_loop} == {
        "hvtpu:ssm.scan"}
    assert {"hvtpu:ssm.proj", "hvtpu:ssm.conv", "hvtpu:ssm.scan",
            "hvtpu:ssm.gate", "hvtpu:mlp", "hvtpu:attention",
            "hvtpu:lm_head"} <= set(by_instruction.values())


def test_a_mamba_layers_instructions_lie_under_its_scopes(v5e_2x2):
    """Two Mamba layers alone at the cell's widths and tokens, forward
    and backward: every instruction of the layers' loop that the layer
    wrote, and not ``lax.scan`` around it, carries one of ``hvtpu:ssm.
    proj|conv|scan|gate`` or ``hvtpu:mlp``, so that ``ssm_ms_per_step``
    and the MLP's scope hold the layer's whole cost."""
    from benchmark import cells, scopes

    cell = cells.load_cell(HYBRID_CELL)
    config = {**cell.config, "num_hidden_layers": 2,
              "layer_types": ["mamba", "mamba"]}
    text = _compiled_step(cell, v5e_2x2, config).as_text()
    by_instruction = scopes.scope_by_instruction(text)
    in_a_layer = [(name, op_name) for name, op_name in _in_loops(text, 1)
                  if op_name.rsplit("/", 1)[-1] not in SCAN_PLUMBING]
    assert len(in_a_layer) > 1000
    outside = [pair for pair in in_a_layer
               if pair[0] not in by_instruction]
    assert outside == [], outside
    assert {by_instruction[name] for name, _ in in_a_layer} == {
        "hvtpu:ssm.proj", "hvtpu:ssm.conv", "hvtpu:ssm.scan",
        "hvtpu:ssm.gate", "hvtpu:mlp"}


# -- the looped cell (models/looped.py) ---------------------------------------

LOOPED_CELL = "ouro-2.6b-6of48-t8k-b1"


def test_the_looped_cells_step_fits_and_keeps_one_exits_logits(v5e_2x2):
    """The whole step of ``ouro-2.6b-6of48-t8k-b1`` as ``benchmark/job
    .py`` builds it, 510 M parameters trained at 12 bytes each, six
    layers walked four times over 8,192 tokens and the whole vocabulary
    after every pass, for one described chip.  By the figure the other
    cells' cases use it needs 11.5 GB (15.0 GB when the label's logit
    was a ``take_along_axis``, whose gradient is a scatter into 1.6 GB
    of zeros: PERF.md, findings of PR 34).  An exit's f32 logits,
    ``[8192, 49152]``, are made and used inside one pass: no loop
    carries such an array from one pass to the next and none is stacked
    over the passes, so no more than one exit's are live, forward or
    backward.  Its attention, 16 key/value heads of one query head each
    with document ids, runs in the three kernels of
    ``ops/flash_attention.py`` under ``hvtpu:attention``, the recomputed
    forward too, none with kernel metadata, eight heads to a block (a
    grid of two blocks of heads: ``lse`` and ``delta`` ``[1, 2, 8192,
    8]``, every block of queries, keys and values ``[512, 1024]``, which
    Mosaic fits in the VMEM the kernels ask for); no scatter is as large
    as the logits; and the five scopes the cell's readers join are
    there."""
    import re

    from benchmark import cells, scopes

    compiled = _compiled_step(cells.load_cell(LOOPED_CELL), v5e_2x2)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) <= 14e9
    text = compiled.as_text()
    logits = re.findall(
        r"^\s*(?:ROOT\s+)?%(\S+) = (\S*f32\[(?:1,)?8192,49152\]\S*) "
        r"([\w-]+)\(", text, re.MULTILINE)
    assert logits
    loops = re.findall(r"^\s*(?:ROOT\s+)?%\S+ = (\(.*?\)) while\(", text,
                       re.MULTILINE)
    assert len(loops) >= 4              # passes and layers, there and back
    assert not any("8192,49152]" in carried for carried in loops)
    assert not re.search(r"\[4,(?:1,)?8192,49152\]", text)
    assert not any(op == "scatter" for _, _, op in logits)
    assert _attention_kernels_by_scope(text) == {
        kernel: {"hvtpu:attention"} for kernel in ATTENTION_KERNELS}
    kernels = re.findall(
        r"^\s*(?:ROOT\s+)?%hvtpu_flash_attention_\w+(?:\.\d+)? = .*$", text,
        re.MULTILINE)
    assert len(kernels) == 4            # forward, recomputed, dq, dk/dv
    assert not any("kernel_metadata" in line.replace(
        "kernel_metadata={}", "") for line in kernels)
    for line in kernels:                # a row's statistics, by block
        shapes = set(re.findall(r"f32\[1,(\d+),(\d+),(\d+)\]", line))
        assert shapes == ({("2", "8", "8192")} if "_dkv" in line
                          else {("2", "8192", "8")}), line
    assert {"hvtpu:loop.proj", "hvtpu:loop.mlp", "hvtpu:loop.exit",
            "hvtpu:attention", "hvtpu:lm_head"} <= set(
                scopes.scope_by_instruction(text).values())


# -- the stack of one-mixer layers (models/hybrid_moe.py) ---------------------

HYBRID_MOE_CELL = "nemotron-3-nano-30b-a3b-9of52-t8k-b2"


def test_the_one_mixer_cells_step_fits_and_its_kernels_keep_their_scope(
        v5e_2x2):
    """The whole step of ``nemotron-3-nano-30b-a3b-9of52-t8k-b2`` as
    ``benchmark/job.py`` builds it, 667 M parameters trained at 12 bytes
    each and 16,384 tokens a step, for one described chip: by the figure
    the other cells' cases use it needs 12.6 GB (the allocator on the
    chip counts 12.3: PERF.md, findings of PR 38).  Its attention, 32
    query heads on 2 key/value heads of 128 with document ids, runs in
    the three kernels of ``ops/flash_attention.py`` under
    ``hvtpu:attention``, the recomputed forward too, none with kernel
    metadata, sixteen query heads and one key/value head to a grid step
    (a grid of two blocks of heads: ``lse`` and ``delta`` ``[2, 2, 8192,
    16]``), which Mosaic fits in the VMEM the kernels ask for.  The
    ungated experts' products are the two-weight kernels of
    ``ops/grouped_ffn.py``, a forward and a backward one for each of
    the four expert layers, under ``hvtpu:moe.experts``: no
    ``ragged-dot`` kernel of XLA's is left, and nothing outside the
    kernels fills, copies or passes over a ``[98304, .]`` buffer (six
    rows a token, of which an even routing uses a sixteenth); the
    experts' weights are filled from 1,856 to 1,920 columns in
    bfloat16 and their float32 gradients keep the parameters' shapes;
    the row buffers are the expert layer's own kernels; and the scopes
    the cell's readers join are there."""
    import re

    from benchmark import cells, scopes

    from horovod_tpu.parallel import moe

    cell = cells.load_cell(HYBRID_MOE_CELL)
    compiled = _compiled_step(cell, v5e_2x2)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) <= 12.7e9
    text = compiled.as_text()
    assert _attention_kernels_by_scope(text) == {
        kernel: {"hvtpu:attention"} for kernel in ATTENTION_KERNELS}
    kernels = re.findall(
        r"^\s*(?:ROOT\s+)?%hvtpu_\w+(?:\.\d+)? = .*$", text, re.MULTILINE)
    attention = [line for line in kernels if "%hvtpu_flash_attention" in line]
    assert len(attention) == 4          # forward, recomputed, dq, dk/dv
    assert any("%hvtpu_moe_row_buffer" in line for line in kernels)
    assert not any("kernel_metadata" in line.replace(
        "kernel_metadata={}", "") for line in kernels)
    for line in attention:              # a row's statistics, by block
        shapes = set(re.findall(r"f32\[2,(\d+),(\d+),(\d+)\]", line))
        assert shapes == ({("2", "16", "8192")} if "_dkv" in line
                          else {("2", "8192", "16")}), line
    assert not re.findall(r"^\s*%(ragged-dot\S*) = ", text, re.MULTILINE)
    _the_experts_products_are_the_grouped_kernels(text, layers=4)
    rows = moe.buffer_rows(
        cell.traffic["batch_per_chip"] * cell.traffic["sequence_length"],
        cell.config["num_experts_per_tok"], cell.config["n_routed_experts"])
    assert rows == 98304
    _no_pass_over_a_whole_row_buffer(text, cell, rows)
    assert re.search(r"bf16\[8,2688,1920\]", text)
    assert not re.search(r"f32\[\d+,1920\]|f32\[\d+,1856\]", text)
    assert {"hvtpu:ssm.proj", "hvtpu:ssm.conv", "hvtpu:ssm.scan",
            "hvtpu:ssm.gate", "hvtpu:attention", "hvtpu:attn.proj",
            "hvtpu:moe.route", "hvtpu:moe.dispatch", "hvtpu:moe.experts",
            "hvtpu:moe.combine", "hvtpu:moe.shared", "hvtpu:lm_head"} <= set(
                scopes.scope_by_instruction(text).values())


KIMI_LINEAR_CELL = "kimi-linear-48b-a3b-5of27-t8k-b2"


def test_the_kimi_linear_cells_step_fits_and_says_which_paths_it_took(
        v5e_2x2):
    """The whole step of ``kimi-linear-48b-a3b-5of27-t8k-b2`` as
    ``benchmark/job.py`` builds it, 602 M parameters trained at 12 bytes
    each and 16,384 tokens a step, for one described chip: XLA:TPU fits
    it in the 15.75 GiB it has (it refuses a program that does not; the
    allocator on the chip counts 15.9 GB at the peak: PERF.md, findings
    of PR 40), 4.8 GB of it parameters and momentum that the step
    updates in place.  Its latent attention, 32 heads with keys of 192
    on values of 128 and document ids, runs in the three kernels of
    ``ops/flash_attention.py`` under ``hvtpu:attention``, the recomputed
    forward too, none with kernel metadata, eight heads to a grid step
    (``lse`` and ``delta`` ``[2, 4, 8192, 8]``): ``q``, ``k``, ``dq``
    and ``dk`` are ``32 x 192 = 6144`` wide, ``v``, the result and
    ``dv`` ``32 x 128 = 4096``, and nothing is filled to 256.  The gated
    experts' products at 2304 x 1024 do not fit the grouped kernels'
    VMEM rule, so they are XLA's own ``ragged-dot`` kernels (the reader
    of ``gated_experts_ms_per_step`` finds them by name); the delta rule
    runs in the two kernels of ``ops/delta_rule.py`` under
    ``hvtpu:kda.delta``, the forward one twice a run of KDA layers
    (forward, and recomputed for the backward one), and no f32 array of
    the rule's chunks (``[2, 32, 128, 64, 128]``, the XLA form's) is left
    under that scope; and the scopes the cell's readers join are
    there."""
    import re

    from benchmark import cells, scopes

    from benchmark.builders import kimi_linear_lm
    from horovod_tpu.models import kimi_linear as kl
    from horovod_tpu.ops import grouped_ffn
    from horovod_tpu.parallel import moe

    cell = cells.load_cell(KIMI_LINEAR_CELL)
    compiled = _compiled_step(cell, v5e_2x2)
    m = compiled.memory_analysis()
    assert 4.8e9 <= m.argument_size_in_bytes <= 4.83e9
    assert m.alias_size_in_bytes >= 4.8e9
    text = compiled.as_text()
    assert _attention_kernels_by_scope(text) == {
        kernel: {"hvtpu:attention"} for kernel in ATTENTION_KERNELS}
    kernels = re.findall(
        r"^\s*(?:ROOT\s+)?%hvtpu_\w+(?:\.\d+)? = .*$", text, re.MULTILINE)
    attention = [line for line in kernels if "%hvtpu_flash_attention" in line]
    assert len(attention) == 4          # forward, recomputed, dq, dk/dv
    assert not any("kernel_metadata" in line.replace(
        "kernel_metadata={}", "") for line in kernels)
    for line in attention:
        assert set(re.findall(r"bf16\[2,8192,(\d+)\]", line)) == {
            "4096", "6144"}, line
        assert not re.search(r"bf16\[2,8192,8192\]", line)    # 32 x 256
        shapes = set(re.findall(r"f32\[2,(\d+),(\d+),(\d+)\]", line))
        assert shapes == ({("4", "8", "8192")} if "_dkv" in line
                          else {("4", "8192", "8")}), line
    for kernel, result in (("_dq", "bf16[2,8192,6144]"),
                           ("_dkv", "(bf16[2,8192,6144]")):
        line, = (line for line in attention if kernel in line)
        assert line.split(" = ")[1].startswith(result), line
    tokens = cell.traffic["batch_per_chip"] * cell.traffic["sequence_length"]
    assert not grouped_ffn.supports(jnp.bfloat16, 2304, 1024, moe.buffer_rows(
        tokens, 8, 8), 128)
    assert moe.products_path(
        jnp.bfloat16, 2304, 1024, tokens, 8, 8, "gated") == "ragged_dot"
    assert re.findall(r"^\s*%(ragged-dot\S*) = ", text, re.MULTILINE)
    assert not re.findall(r"%hvtpu_grouped_ffn", text)
    delta = [re.search(r"%(hvtpu_delta_rule_\w+?)(?:\.\d+)? =", line)[1]
             for line in kernels if "%hvtpu_delta_rule" in line]
    groups = sum(mixer == "kda" for mixer, _, _ in kl.layer_groups(
        *kimi_linear_lm.layer_kinds(cell.config)))
    assert sorted(delta) == (["hvtpu_delta_rule_bwd"] * groups
                             + ["hvtpu_delta_rule_fwd"] * 2 * groups)
    by_instruction = scopes.scope_by_instruction(text)
    assert {scope for name, scope in by_instruction.items()
            if name.startswith("hvtpu_delta_rule")} == {"hvtpu:kda.delta"}
    assert not [name for name, scope in by_instruction.items()
                if scope == "hvtpu:kda.delta" and re.search(
                    r"^\s*(?:ROOT\s+)?%" + re.escape(name)
                    + r" = f32\[2,32,128,64,128\]", text, re.MULTILINE)]
    assert {"hvtpu:kda.proj", "hvtpu:kda.conv", "hvtpu:kda.gate",
            "hvtpu:kda.delta", "hvtpu:mla.proj", "hvtpu:attention",
            "hvtpu:mlp", "hvtpu:moe.route", "hvtpu:moe.dispatch",
            "hvtpu:moe.experts", "hvtpu:moe.combine", "hvtpu:moe.shared",
            "hvtpu:lm_head"} <= set(
                scopes.scope_by_instruction(text).values())
