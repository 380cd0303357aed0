"""The served MiMo-V2.5 programs compiled at their real widths for a
TPU v5e that is described, not attached (ISSUE 27; on-chip-measurement
guide, section 2): the fused decode step over 128 slots on the 4,096
rung, the cohort prefill of one and of two prompts in the largest
bucket, and the expert product both ways. What the chip's compiler
would refuse (a program that does not fit 16 GB, a grouped product it
cannot lower) it refuses here, at no chip time. Nothing runs: no
result and no time comes out of these. Since ISSUE 28 also GPT-2's
served programs at both serving cells' geometry: what they hold that
moves a whole layer of the slab.

The topology is described inside a fixture, after collection, and only
in this file: one process may hold the TPU's library (see the guide).
"""
import json
import os
import re

import numpy as np
import pytest

HBM = 16e9
CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "configs", "mimo-v2.5.json")
SLOTS, RUNG, BUCKET = 128, 4096, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache and cannot be read back without one: keep it off here."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _served(config_path, slots, rung, one_chip):
    """A configuration's model (nothing drawn), its parameter tree and
    its slab as shapes on the described chip."""
    import jax

    from perfbench.harness import cell
    from singa_tpu import tensor

    from singa_tpu.ops import pallas_kernels

    with open(config_path) as f:
        config = json.load(f)
    model = cell.build(config["builder"])
    tensor.set_matmul_precision(config["serve"]["matmul_precision"])
    # the process's backend is the CPU, where the kernels would be
    # interpreted: what is compiled here is what the chip would run
    monkey = pytest.MonkeyPatch()
    monkey.setattr(pallas_kernels, "_interpret", lambda: False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    table = {name: sds(shape, dtype)
             for name, shape, dtype, _, _ in model._param_table()}
    params = model._tree(table.__getitem__)
    slab = jax.eval_shape(
        lambda: model.new_slab(params, slots, rung, None))
    slab = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), slab)
    yield model, params, slab, sds
    monkey.undo()
    tensor.set_matmul_precision("highest")


@pytest.fixture(scope="module")
def served(one_chip):
    yield from _served(CONFIG, SLOTS, RUNG, one_chip)


def _as_served(model, monkey):
    """`decode_step`, `decode_scan` and `prefill_slab` build and donate
    as for `ServingEngine`; the returned list keeps what they compiled
    (shapes go in, so nothing runs and nothing is cached)."""
    compiled = []
    monkey.setattr(model, "_aot_step",
                   lambda kind, jitted, args, extras: compiled.append(
                       jitted.lower(*args).compile()) or (lambda *a: a))
    monkey.setattr(model, "_program_cache", dict)
    return compiled


def _fits(compiled, what):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM, (
        f"{what}: {total / 1e9:.2f} GB (arguments "
        f"{m.argument_size_in_bytes / 1e9:.2f}, temporaries "
        f"{m.temp_size_in_bytes / 1e9:.2f}) does not fit {HBM / 1e9} GB")
    return m


def test_decode_step_compiles_and_fits_with_the_slab_donated(served):
    import jax

    model, params, slab, sds = served
    vec = sds((SLOTS,), np.int32)
    compiled = jax.jit(model._slot_step, donate_argnums=1).lower(
        params, slab, vec, vec).compile()
    m = _fits(compiled, "decode step")
    # the slab is updated in place (`cache_write`): no second copy
    # beside the first, and no whole layer of it among the temporaries
    slab_bytes = sum(v for v in model.slab_bytes(slab).values())
    assert m.alias_size_in_bytes >= slab_bytes
    assert m.temp_size_in_bytes < 0.5e9


def test_run_ahead_block_reads_the_experts_as_stored(served, monkeypatch):
    """A block of 2 steps: no held expert's matrix is copied into
    another layout and no second slab sits among the temporaries
    (around a loop XLA does both: `HybridWindowMoELM.scan_unroll`)."""
    model, params, slab, sds = served
    vec = sds((SLOTS,), np.int32)
    lowered = _as_served(model, monkeypatch)
    model.decode_scan(params, slab, vec, vec, 2)
    (compiled,) = lowered
    E, d, f = model.held[1], model.d_model, model.d_ff_expert
    copies = re.findall(rf"= bf16\[{E},(?:{d},{f}|{f},{d})\]\S* copy\(",
                        compiled.as_text())
    assert not copies
    assert _fits(compiled, "block of 2 steps").temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("rows", [1, 2])
def test_cohort_prefill_of_the_largest_bucket_compiles_and_fits(served,
                                                                 rows):
    import jax

    model, params, slab, sds = served
    compiled = jax.jit(model._prefill_rows, donate_argnums=1).lower(
        params, slab, sds((rows, BUCKET), np.int32), sds((rows,), np.int32),
        sds((rows,), np.int32)).compile()
    _fits(compiled, f"prefill of {rows} x {BUCKET}")


@pytest.mark.parametrize("rows", [SLOTS, 2 * BUCKET],
                         ids=["decode_rows_dense", "prefill_rows_sorted"])
def test_expert_product_compiles_at_real_widths(served, rows):
    """128 rows: every held expert over every row. 4,096 rows: sorted
    assignments through `ragged_dot`, which the chip's compiler lowers
    to its own grouped kernel (no dense [experts, rows] expansion)."""
    import jax

    model, params, _, sds = served
    ffn = params["blocks"][1]["ffn"]
    x = sds((rows, model.d_model), ffn["W_g"].dtype)
    compiled = jax.jit(
        lambda f, x: model._experts(f, x, "default")).lower(ffn, x).compile()
    _fits(compiled, f"expert product over {rows} rows")
    if rows > model.dense_rows:
        flops = compiled.cost_analysis()["flops"]
        K, d, f = (model.experts_per_token, model.d_model,
                   model.d_ff_expert)
        # the buffer's rows * K assignments, once: not times 16 experts
        assert flops < 1.5 * (2 * rows * K * 3 * d * f)


# -- LFM2-24B-A2B's served programs at their real widths (ISSUE 32) ------------
LFM2 = os.path.join(os.path.dirname(CONFIG), "lfm2-24b-a2b.json")
LFM2_RUNG, LFM2_BUCKET = 2048, 1024


@pytest.fixture(scope="module")
def lfm2(one_chip):
    """`lfm2-24b-a2b-serve-decode128`'s geometry: 128 slots on the
    2,048 rung, prompts up to the 1,024 bucket."""
    yield from _served(LFM2, SLOTS, LFM2_RUNG, one_chip)


def _lfm2_moves(model, slab, text):
    """Opcodes of what moves a whole attention layer of the slab, or
    rebuilds a whole convolution state (a `copy` or `transpose` with a
    state's shape, a `dynamic-update-slice` whose update is a whole
    state; a prefill's write of one row is the write itself). Reading
    a 1 MB state is not moving it: XLA prefetches it into VMEM by a
    `copy-start`, as it does the weights."""
    from singa_tpu.models.shortconv_moe import CONV

    layer = min(int(np.prod(c["k"].shape))
                for kind, c in zip(model.layer_types, slab) if kind != CONV)
    moves = _whole_layer_moves(text, layer)
    state = ",".join(str(d) for d in slab[0]["u"].shape)
    whole, size = int(np.prod(slab[0]["u"].shape)), {}
    for name, dims, opcode, operands in _INSTRUCTION.findall(text):
        size[name] = int(np.prod([int(d) for d in dims.split(",") if d]))
        if dims != state:
            continue
        if opcode in ("copy", "transpose"):
            moves.append(opcode)
        elif opcode == "dynamic-update-slice":
            update = operands.split(",")[1].strip().lstrip("%")
            if size.get(update, whole) >= whole:
                moves.append(opcode)
    return moves


def _engine_program(model, params, slab, sds, monkeypatch, slots, program,
                    prefill=None):
    """The executable `ServingEngine` would get for `program`: "step",
    "block<k>", or a prefill of `prefill` = (rows, bucket)."""
    lowered = _as_served(model, monkeypatch)
    vec = sds((slots,), np.int32)
    if program == "step":
        model.decode_step(params, slab, vec, vec)
    elif program.startswith("block"):
        model.decode_scan(params, slab, vec, vec, int(program[5:]))
    else:
        rows, bucket = prefill
        model.prefill_slab(params, slab, sds((rows, bucket), np.int32),
                           sds((rows,), np.int32), sds((rows,), np.int32))
    (compiled,) = lowered
    return compiled


def _lfm2_program(model, params, slab, sds, program, monkeypatch):
    return _engine_program(
        model, params, slab, sds, monkeypatch, SLOTS, program,
        (int(program[7:]), LFM2_BUCKET) if program[:7] == "prefill" else None)


@pytest.mark.parametrize("program,temporaries", [
    ("step", 0.1e9), ("block2", 0.1e9), ("block8", 0.3e9),
    ("prefill1", 0.2e9), ("prefill2", 0.3e9)])
def test_lfm2_programs_hold_the_slab_in_place(lfm2, program, temporaries,
                                              monkeypatch):
    """The fused step, run-ahead blocks of 2 and 8 and the cohort
    prefill of one and of two 1,024-token prompts, over 10.36 GB of
    weights and the 1.08 GB slab: each fits, the slab (two contexts and
    seven states) is aliased whole, no operation moves a whole layer or
    rebuilds a whole state, no held expert's matrix is copied into
    another layout (a block is its steps in a row: as a loop it does
    not fit, 17.7 GB), and the temporaries are what is stated: 0.02 GB
    a step (with the values held [T, 64] the step carried both layers
    re-laid, 0.55 GB), 0.04 and 0.12 GB a block, 0.06 and 0.08 GB a
    prefill (compiled for a described v5e; no chip, no device metric)."""
    model, params, slab, sds = lfm2
    compiled = _lfm2_program(model, params, slab, sds, program, monkeypatch)
    m = _fits(compiled, f"LFM2 {program}")
    by_kind = model.slab_bytes(slab)
    assert by_kind == {"context": 2 * 2 * 128 * 8 * 64 * 2048 * 2,
                       "state": 7 * 128 * 2 * 2048 * 2}
    assert m.alias_size_in_bytes >= sum(by_kind.values())
    assert m.temp_size_in_bytes < temporaries
    text = compiled.as_text()
    assert not _lfm2_moves(model, slab, text)
    E, d, f = model.held[1], model.d_model, model.d_ff_expert
    assert not re.findall(rf"= bf16\[{E},(?:{d},{f}|{f},{d})\]\S* copy\(",
                          text)


@pytest.mark.parametrize("rows", [SLOTS, 2 * LFM2_BUCKET],
                         ids=["decode_rows_dense", "prefill_rows_sorted"])
def test_lfm2_expert_product_compiles_at_real_widths(lfm2, rows):
    """The one routed layer with all 64 experts held. 128 rows: every
    expert over every row (weight-bound). 2,048 rows: all 8,192 sorted
    assignments straight through `ragged_dot`, once: no quarter-rows
    branch, and not times 64 experts."""
    import jax

    model, params, _, sds = lfm2
    ffn = params["blocks"][1]["ffn"]
    x = sds((rows, model.d_model), ffn["W_g"].dtype)
    compiled = jax.jit(
        lambda f, x: model._experts(f, x, "default")).lower(ffn, x).compile()
    _fits(compiled, f"LFM2 expert product over {rows} rows")
    if rows > model.dense_rows:
        K, d, f = (model.experts_per_token, model.d_model,
                   model.d_ff_expert)
        assert compiled.cost_analysis()["flops"] \
            < 1.5 * (2 * rows * K * 3 * d * f)
        assert " conditional(" not in compiled.as_text()


# -- GPT-2's served programs update the slab where it lies (ISSUE 28) ----------
GPT2 = os.path.join(os.path.dirname(CONFIG), "gpt2.json")


@pytest.fixture(scope="module")
def gpt2(one_chip):
    """`perfbench/configs/gpt2.json`'s model and its decode-params
    tree as shapes on the described chip, at the serving policy."""
    import jax

    from perfbench.harness import cell
    from singa_tpu import device, tensor
    from singa_tpu.ops import pallas_kernels

    with open(GPT2) as f:
        config = json.load(f)
    before = tensor.get_matmul_precision()
    tensor.set_matmul_precision(config["serve"]["matmul_precision"])
    model = cell.build(config["builder"])
    model.compile([tensor.from_numpy(
        np.zeros((1, 4), np.int32), device=device.get_default_device())],
        is_train=False, use_graph=False)
    model.eval()
    monkey = pytest.MonkeyPatch()
    monkey.setattr(pallas_kernels, "_interpret", lambda: False)
    compiled = _as_served(model, monkey)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                    model._decode_params())
    yield model, params, sds, compiled
    monkey.undo()
    tensor.set_matmul_precision(before)


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \(?\w+\[([\d,]*)\][^=]*? ([\w-]+)\(([^)]*)\)",
    re.M)   # name, dims (a tuple's first), opcode, operands


def _whole_layer_moves(text, layer):
    """Opcodes of the instructions of a compiled program that move a
    slab layer's elements or more: every `copy` (`copy-start`: one the
    compiler made asynchronous) and `transpose`, and every
    `dynamic-update-slice` but one whose update is smaller than a
    layer (rows written in place: that is the write itself)."""
    size, moves = {}, []
    for name, dims, opcode, operands in _INSTRUCTION.findall(text):
        size[name] = n = int(np.prod([int(d) for d in dims.split(",") if d]))
        if n < layer:
            continue
        if opcode in ("copy", "copy-start", "transpose"):
            moves.append(opcode)
        elif opcode == "dynamic-update-slice":
            update = operands.split(",")[1].strip().lstrip("%")
            if size.get(update, layer) >= layer:
                moves.append(opcode)
    return moves


@pytest.mark.parametrize("program", ["step", "block8", "block2", "prefill"])
@pytest.mark.parametrize("slots,rung", [(32, 1024), (64, 256)],
                         ids=["decode_cell_32x1024", "short_cell_64x256"])
def test_gpt2_programs_move_no_whole_layer_but_the_write(gpt2, slots, rung,
                                                         program):
    """The fused step, a run-ahead block (k = 8 and 2) and the 2 x 128
    cohort prefill over the slab of `gpt2-serve-decode` (32 x 1024)
    and `gpt2-serve-short` (64 x 256): the slab is aliased whole,
    nothing of a layer's size sits among the temporaries (a block's
    0.3 GB are its bfloat16 weights, converted once before the loop),
    and no operation moves a whole layer. With a layer [2, B, H, T, D]
    every program held 24 whole-layer copies, in and out around each
    write (PERF.md, PR 28). Since ISSUE 30 a step's attention is
    `decode_attend`, compiled here by Mosaic at both geometries: the
    scores stay in its VMEM, so no instruction of a decode program has
    a [B, H, T] result any more (the score fusion is gone). The one
    exception PR 28 pinned went with it: on the short cell's rung a
    layer (101 MB) fits the chip's 128 MiB of VMEM, and inside a
    block's loop XLA passed 3 of the 12 layers through it for its own
    two fusions, each written back whole by an asynchronous copy; the
    kernel takes the layer where it lies in HBM, so XLA stages none."""
    import jax

    model, params, sds, compiled = gpt2
    slab = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: model.new_slab(params, slots, rung, None)))
    vec = sds((slots,), np.int32)
    del compiled[:]
    if program == "step":
        model.decode_step(params, slab, vec, vec)
    elif program == "prefill":
        two = sds((2,), np.int32)
        model.prefill_slab(params, slab, sds((2, 128), np.int32), two, two)
    else:
        model.decode_scan(params, slab, vec, vec, int(program[5:]))
    (exe,) = compiled
    m = _fits(exe, f"GPT-2 {program} at {slots} x {rung}")
    assert m.alias_size_in_bytes >= model.slab_bytes(slab)["context"]
    assert m.temp_size_in_bytes < 0.5e9
    text = exe.as_text()
    assert not _whole_layer_moves(text, int(np.prod(slab[0].shape)))
    if program != "prefill":
        heads = model.blocks._seq[0].attn.num_heads
        assert len(re.findall(r"%decode_attend\S* = ", text)) >= len(slab)
        assert not re.findall(rf"= \w+\[{slots},{heads},{rung}\]", text)


# -- the token program of a single greedy step (ISSUE 33) ----------------------
@pytest.mark.parametrize("cell_", ["mimo-v2.5-serve-mixedlen",
                                   "lfm2-24b-a2b-serve-decode128",
                                   "gpt2-serve-decode", "gpt2-serve-short",
                                   "evabyte-serve-longctx32"])
def test_the_token_program_is_the_step_and_an_argmax(request, cell_,
                                                     monkeypatch):
    """`decode_scan(k=1)`, what `ServingEngine` dispatches for a single
    step while every live session is greedy, beside `decode_step` at
    the cell's geometry, both as the engine gets them: the result is
    [1, slots] int32 where the step's is [slots, vocabulary] float32
    (33.6 MB in the lfm2 cell), the slab is aliased whole by both, and
    the device pays the step and an argmax: no more bytes moved than
    the step moves (the logits are not written out), a hundredth more
    operations at most, and temporaries that hold the logits the step
    had among its results and little else: 0.024 GB for GPT-2, where a
    block as a loop holds 0.3 GB of weights converted once before it
    (compiled for a described v5e; no chip, no device metric)."""
    import jax

    if cell_.startswith("gpt2"):
        model, params, sds, compiled = request.getfixturevalue("gpt2")
        slots, rung = (32, 1024) if cell_ == "gpt2-serve-decode" else (64, 256)
        slab = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype),
            jax.eval_shape(lambda: model.new_slab(params, slots, rung, None)))
        del compiled[:]
    else:
        fixture = {"lfm2": "lfm2", "evab": "evabyte"}.get(cell_[:4], "served")
        model, params, slab, sds = request.getfixturevalue(fixture)
        slots = EVA_SLOTS if fixture == "evabyte" else SLOTS
        compiled = _as_served(model, monkeypatch)
    vec = sds((slots,), np.int32)
    model.decode_step(params, slab, vec, vec)
    model.decode_scan(params, slab, vec, vec, 1)
    step, token = compiled
    logits, toks = step.out_info[0], token.out_info[0]
    assert (toks.shape, toks.dtype) == ((1, slots), np.int32)
    assert (logits.shape[0], logits.dtype) == (slots, np.float32)
    ms = _fits(step, f"{cell_} step")
    mt = _fits(token, f"{cell_} token program")
    slab_bytes = sum(model.slab_bytes(slab).values())
    assert mt.alias_size_in_bytes == ms.alias_size_in_bytes >= slab_bytes
    logits_bytes = 4 * int(np.prod(logits.shape))
    assert mt.output_size_in_bytes <= ms.output_size_in_bytes - logits_bytes \
        + 4096
    assert mt.temp_size_in_bytes < ms.temp_size_in_bytes + logits_bytes \
        + 0.02e9
    cs, ct = step.cost_analysis(), token.cost_analysis()
    assert ct["bytes accessed"] <= cs["bytes accessed"]
    assert cs["flops"] <= ct["flops"] < 1.01 * cs["flops"]
    assert " while(" not in token.as_text()


def _training_step(config, builder, example, batch, one_chip):
    """A configuration's training step at its `train` policy, compiled
    for the described chip over `batch` (shapes): the program the first
    `model(x, y)` of `perfbench/drivers/train.py` makes, kernels
    lowered by Mosaic. `example`: a tiny host batch to build the
    model's parameters from."""
    import jax

    from perfbench.harness import cell
    from singa_tpu import device, tensor
    from singa_tpu.model import _JitStep
    from singa_tpu.ops import pallas_kernels

    saved = (tensor.get_matmul_precision(), tensor.get_compute_dtype(),
             pallas_kernels.enabled())
    monkey = pytest.MonkeyPatch()
    monkey.setattr(pallas_kernels, "_interpret", lambda: False)
    try:
        cell.set_policies(config["train"])
        dev = device.get_default_device()
        model = cell.build(builder)
        model.set_optimizer(cell.build(config["train"]["optimizer"]))
        model.compile([tensor.from_numpy(example, device=dev)],
                      is_train=True, use_graph=True)
        step = _JitStep(model)     # as the first `model(x, y)` makes it

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

        return step._build(*batch).lower(
            [sds(p.data) for p in step.params],
            [sds(s.data) for s in step.states],
            [sds(o) for o in step._opt_arrays()], sds(dev._rng_key), 0,
            tuple(sds(a) for a in batch)).compile()
    finally:
        monkey.undo()
        tensor.set_matmul_precision(saved[0])
        tensor.set_compute_dtype(saved[1])
        pallas_kernels.enable(saved[2])


def test_the_one_chip_resnet_step_holds_what_the_memory_meter_misses(
        one_chip):
    """The one-chip ResNet-50 step at batch 256 (ISSUE 27's second
    cell, taken out again): the runtime's `peak_bytes_in_use` counts
    live buffers and read 0.73 GB on the chip, under the floor a new
    cell has; the step itself holds its temporaries besides, over half
    the chip, and fits (PERF.md, sections 4 and 7: the number given
    there is this compile's)."""
    import jax

    with open(os.path.join(os.path.dirname(CONFIG), "resnet50.json")) as f:
        config = json.load(f)
    B = 256
    compiled = _training_step(
        config, config["builder"], np.zeros((2, 3, 224, 224), np.float32),
        (jax.ShapeDtypeStruct((B, 3, 224, 224), np.float32),
         jax.ShapeDtypeStruct((B,), np.int32)), one_chip)
    m = _fits(compiled, f"ResNet-50 training step of {B}")
    assert m.temp_size_in_bytes > 0.25 * 16 * 2**30


def test_the_gpt2_training_step_moves_its_logits_in_bfloat16(one_chip):
    """`gpt2-train-seq1024`'s step (ISSUE 38): the cell's policy
    (`perfbench/configs/gpt2.json` `train`: bfloat16 AMP, the Pallas
    tier on, Adam), 8 x 1,024 tokens, the published widths, two layers
    (depth does not touch the head). Both fused softmax-xent kernels
    lower under Mosaic at bfloat16 [8192, 50257], 16 whole rows a
    block, and no float32 array of the logits' size is left: the cast
    in front of the forward kernel, the float32 d-logits behind the
    backward one and the convert back are gone. What stays of that
    size, all bfloat16: the tied head's product, which XLA writes
    vocabulary-major; its two relayout copies, one into the row-major
    operand a kernel must have and one into the layout the compiler
    gives the step's result (`out, loss = model(x, y)` returns the
    logits); the backward kernel's d-logits (PERF.md, PR 38)."""
    import jax

    with open(GPT2) as f:
        config = json.load(f)
    B, S, V = 8, 1024, config["vocab_size"]
    tokens = jax.ShapeDtypeStruct((B, S), np.int32)
    compiled = _training_step(
        config, dict(config["builder"], kwargs=dict(
            config["builder"]["kwargs"], num_layers=2)),
        np.zeros((1, 8), np.int32), (tokens, tokens), one_chip)
    m = _fits(compiled, "GPT-2 training step of 8 x 1024, two layers")
    # 3.91 GB with the float32 logits and d-logits live together
    assert m.temp_size_in_bytes < 2.6e9
    text = compiled.as_text()
    logits = rf"\[(?:{B * S}|{B},{S}),{V}\]"
    assert not re.search("f32" + logits, text)
    entry = text[text.index("\nENTRY "):]
    for kernel in ("softmax_xent_fwd", "softmax_xent_bwd"):
        (call,) = re.findall(rf"%{kernel}\S* = .*custom-call\((.*)", entry)
        assert f"bf16[{B * S},{V}]" in call, call
    held = re.findall(rf"= bf16{logits}\S* ([\w-]+)\(", entry)
    assert sorted(set(held) - {"bitcast"}) == ["copy", "custom-call",
                                               "fusion"], held
    assert held.count("copy") == 2, held


@pytest.mark.parametrize("rows,classes", [(8, 10), (24, 1000), (128, 1000),
                                          (64, 50257)])
@pytest.mark.parametrize("dtype,hlo", [("bfloat16", "bf16"),
                                       ("float32", "f32")])
def test_xent_kernels_lower_for_a_classifier_under_amp(
        one_chip, monkeypatch, dtype, hlo, rows, classes):
    """Who else runs the xent kernels (ISSUE 38): a classifier's
    [B, 10] or [B, 1000] logits, bfloat16 under AMP. Mosaic takes the
    block the dtype's tile gives: 8 rows of bfloat16 are the whole
    (short) batch, 24 are padded to two blocks of 16, 128 are one
    block; float32 keeps the tile it had."""
    import jax

    from singa_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((rows, classes), dtype, sharding=one_chip)
    lab = jax.ShapeDtypeStruct((rows,), np.int32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((rows,), np.float32, sharding=one_chip)
    fwd = jax.jit(pk.softmax_xent).lower(x, lab).compile()
    bwd = jax.jit(lambda x, lab, g: pk._softmax_xent_bwd(
        (x, lab), g)[0]).lower(x, lab, g).compile()
    assert "softmax_xent_fwd" in fwd.as_text()
    assert re.search(rf"%softmax_xent_bwd\S* = {hlo}\[\d+,{classes}\]",
                     bwd.as_text())


# -- EvaByte's served programs at their real widths (ISSUE 35) -----------------
EVABYTE = os.path.join(os.path.dirname(CONFIG), "evabyte.json")
EVA_SLOTS, EVA_RUNG = 32, 16384


@pytest.fixture(scope="module")
def evabyte(one_chip):
    """`evabyte-serve-longctx32`'s geometry: 32 slots on the 16,384
    rung, one-prompt prefills up to the 16,384 bucket."""
    yield from _served(EVABYTE, EVA_SLOTS, EVA_RUNG, one_chip)


@pytest.mark.parametrize("program,temporaries", [
    ("step", 0.05e9), ("block1", 0.05e9), ("block8", 0.1e9),
    ("prefill2048", 0.6e9), ("prefill16384", 1.0e9)])
def test_evabyte_programs_hold_the_slab_in_place(evabyte, program,
                                                 temporaries, monkeypatch):
    """The fused step, the token program, a run-ahead block of 8 and
    the one-prompt prefill of the shortest and of the longest bucket,
    over 2.45 GB of weights and the 9.66 GB slab (six layers of 32
    window buffers and 32 summary lists): each fits 16 GB, the slab is
    aliased whole, no operation moves a whole buffer or a whole summary
    list of a layer, and the temporaries are what is stated; a decode
    program holds the length-aware attention kernel, one call a layer,
    and no joint score tensor (compiled for a described v5e; no chip,
    no device metric)."""
    model, params, slab, sds = evabyte
    compiled = _engine_program(
        model, params, slab, sds, monkeypatch, EVA_SLOTS, program,
        (1, int(program[7:])) if program[:7] == "prefill" else None)
    m = _fits(compiled, f"EvaByte {program}")
    by_kind = model.slab_bytes(slab)
    assert by_kind == {"window": 6 * 32 * 2 * 32 * 128 * 2048 * 2,
                       "summary": 6 * 32 * 2 * 32 * 128 * 1024 * 2}
    assert m.alias_size_in_bytes >= sum(by_kind.values())
    assert m.temp_size_in_bytes < temporaries
    text = compiled.as_text()
    # the smaller of a layer's arrays: a summary list
    assert not _whole_layer_moves(text, int(np.prod(slab[0]["sk"].shape)))
    if program == "block1":
        assert " while(" not in text
    if program[:7] != "prefill":
        # since ISSUE 36 a step's attention is `window_summary_attend`,
        # lowered here by Mosaic at the cell's widths: one call a layer
        # that takes the buffers and the lists where they lie (the
        # shapes `step_parts` places it by are in its text), and the
        # joint scores [32, 32, 3072] are nowhere any more
        calls = re.findall(r"^\s*%window_summary_attend\S* = .*$", text, re.M)
        assert len(calls) == len(slab)
        for call in calls:
            assert "custom-call(" in call
            assert call.count("bf16[32,32,128,2048]") == 2
            assert call.count("bf16[32,32,128,1024]") == 2
        assert "[32,32,3072]" not in text


# -- MiniMax-M3's served programs at their real widths (ISSUE 39) --------------
MINIMAX = os.path.join(os.path.dirname(CONFIG), "minimax-m3.json")
MM_SLOTS, MM_RUNG = 20, 32768


@pytest.fixture(scope="module")
def minimax(one_chip):
    """`minimax-m3-serve-longctx20`'s geometry: 20 slots on the 32,768
    rung, one-prompt prefills up to the 32,768 bucket."""
    yield from _served(MINIMAX, MM_SLOTS, MM_RUNG, one_chip)


# what may hold a whole key or value array of a layer: the slab passed
# along, and the two kernels that take it where it lies (the write of
# one position a row, in place, and the attention that fetches the
# selected blocks alone)
_PASSED = ("parameter", "get-tuple-element", "tuple", "bitcast")
_KERNELS = ("cache_write", "selected_blocks_attend")


def _reads_a_whole_context(text, slots, rung):
    """(name, opcode) of the instructions that take or make a whole
    [slots, 4, 128, rung] key or [slots, 4, rung, 128] value array and
    are neither passing the slab along nor one of `_KERNELS`."""
    whole = re.compile(rf"\[{slots},4,(?:128,{rung}|{rung},128)\]")
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = .*? ([\w-]+)\(", line)
        if not m or not whole.search(line):
            continue
        name, opcode = m.groups()
        if opcode in _PASSED or (opcode == "custom-call"
                                 and name.startswith(_KERNELS)):
            continue
        out.append((name, opcode))
    return out


@pytest.mark.parametrize("program,temporaries", [
    ("step", 0.2e9), ("block1", 0.2e9), ("prefill4096", 1.5e9),
    ("prefill32768", 1.5e9)])
def test_minimax_programs_hold_the_slab_in_place(minimax, program,
                                                 temporaries, monkeypatch):
    """The fused step, the token program and the one-prompt prefill of
    the shortest bucket and of the one a 24,576-position prompt takes,
    over 6.39 GB of weights and the 6.76 GB slab (five layers of 20
    slots' keys and values on the 32,768 rung, and their pooled block
    keys in float32): each fits 16 GB, the slab is aliased whole, and the
    temporaries are what is stated. A decode program holds the
    selected-blocks kernel, one call a layer, and no other operation
    reads a whole key or value array of a layer; a prefill holds no
    score array over all the keys of its block of queries (compiled
    for a described v5e; no chip, no device metric)."""
    model, params, slab, sds = minimax
    compiled = _engine_program(
        model, params, slab, sds, monkeypatch, MM_SLOTS, program,
        (1, int(program[7:])) if program[:7] == "prefill" else None)
    m = _fits(compiled, f"MiniMax-M3 {program}")
    by_kind = model.slab_bytes(slab)
    assert by_kind == {"context": 5 * 20 * 2 * 4 * 128 * 32768 * 2,
                       "blockkey": 5 * 20 * 4 * 128 * 256 * 4}
    assert m.alias_size_in_bytes >= sum(by_kind.values())
    assert m.temp_size_in_bytes < temporaries, m.temp_size_in_bytes / 1e9
    text = compiled.as_text()
    if os.environ.get("SINGA_DUMP_HLO"):
        with open(os.path.join(os.environ["SINGA_DUMP_HLO"],
                               f"minimax_{program}.txt"), "w") as f:
            f.write(text)
    if program[:7] != "prefill":
        assert not _reads_a_whole_context(text, MM_SLOTS, MM_RUNG)
        calls = re.findall(r"^\s*%selected_blocks_attend\S* = .*$", text,
                           re.M)
        assert len(calls) == len(slab)
        assert " while(" not in text
        # the step's scopes reach the program's metadata, where a trace
        # joined to the program's own HLO finds them
        for scope in ("msa_indexer", "attn_sparse", "moe_shared",
                      "moe_router", "moe_experts"):
            assert re.search(rf'op_name="jit\(slot_\w+\)/{scope}/', text)
    else:
        # the prompt's rows written into the slab in place, and nothing
        # of a layer's size moved; a tile of 512 queries over a key tile
        # of 512 is the largest score array (over all 32,768 keys it
        # would be 64 times that)
        assert not _whole_layer_moves(text, int(np.prod(slab[0]["k"].shape)))
        assert not re.search(r"f32\[1,4,512,16,(?:4096|32768)\]", text)
