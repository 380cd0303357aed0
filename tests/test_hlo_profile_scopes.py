"""Scopes that survive differentiation, and the join of a device trace
to them (`hlo_profile.scope_of` / `scope_map` / `scope_times` /
`step_programs`): a toy `TransformerLM` and a toy ResNet step lowered
on the CPU — counts and names, never a time."""
import gc
import os
import sys

import numpy as np
import pytest

from singa_tpu import autograd, device, hlo_profile as hp, opt, \
    resilience, stats, tensor
from singa_tpu.models import transformer

_CNN = os.path.join(os.path.dirname(__file__), "..", "examples", "cnn")
sys.path.insert(0, os.path.join(_CNN, "model"))


@pytest.fixture(autouse=True)
def _clean_knobs():
    yield
    stats.configure(step_guard=False, loss_scaling=None)
    resilience.reset_state()
    device.set_remat_policy(None)
    autograd.set_remat(False)


def _lm(clip=False, **compile_kw):
    m = transformer.TransformerLM(97, d_model=32, num_heads=2,
                                  num_layers=2, d_ff=64, max_len=16,
                                  tie_embeddings=True)
    o = opt.Adam(1e-3)
    if clip:
        o.set_clip_norm(1.0)
    m.set_optimizer(o)
    x = tensor.from_numpy(
        np.random.RandomState(0).randint(0, 97, (4, 16)).astype(np.int32))
    m.compile([x], is_train=True, use_graph=True, **compile_kw)
    return m, x, x


def _resnet():
    import resnet

    m = resnet.create_model(depth=18, num_classes=10)
    m.set_optimizer(opt.SGD(0.1, momentum=0.9))
    rs = np.random.RandomState(0)
    x = tensor.from_numpy(rs.randn(4, 3, 32, 32).astype(np.float32))
    y = tensor.from_numpy(rs.randint(0, 10, (4,)).astype(np.int32))
    m.compile([x], is_train=True, use_graph=True)
    return m, x, y


def _labelled(text):
    """(label, scope, dir) of every instruction of the text's
    event-bearing computations that carries metadata."""
    comps = hp._parse_computations(text)
    reach = hp.scope_map(text)["instructions"]
    out = []
    for instrs in comps.values():
        for ins in instrs:
            if ins.name in reach and hp._OPNAME_RE.search(ins.line):
                label = hp._op_label(ins)
                out.append((label,) + hp.scope_of(label))
    return out


# -- scope_of on spelled-out labels -------------------------------------------
@pytest.mark.parametrize("label, want", [
    ("transpose(jvp(LM.blocks.l0.attn/Attention))/bhqk,bhkd->bhqd",
     ("LM.blocks.l0.attn/Attention", "bwd")),
    ("jvp(LM.blocks.l0.attn.q_proj/Mult)/dot_general",
     ("LM.blocks.l0.attn.q_proj/Mult", "fwd")),
    ("LM/SoftMaxCrossEntropy/jit(log_softmax)/reduce_max",
     ("LM/SoftMaxCrossEntropy", "fwd")),
    ("opt/Adam/LM.embed.W/mul", ("opt/Adam/LM.embed.W", "")),
    # a branch's own scope wins over the one around the conditional
    ("opt/guard/cond/branch_1_fun/opt/Adam/LM.embed.W/mul",
     ("opt/Adam/LM.embed.W", "")),
    ("opt/guard/cond", ("opt/guard", "")),
    # a loop's bookkeeping falls to the scope around the loop
    ("opt/accum/while/body/add", ("opt/accum", "")),
    ("opt/accum/while/body/transpose(jvp(LM.l1/Op))/dot", ("LM.l1/Op", "bwd")),
    ("opt/accum/while/body/LM/SoftMaxCrossEntropy/reduce_sum",
     ("LM/SoftMaxCrossEntropy", "fwd")),
    ("transpose(jvp(checkpoint/LM.l1/Op))/mul", ("LM.l1/Op", "bwd")),
    ("transpose(jvp(jvp()))/checkpoint/rematted_computation/LM.l1/Op/mul",
     ("LM.l1/Op", "bwd")),
    # what the parent's programs read: a backward with nothing to
    # place, a forward by its op's class alone
    ("transpose(jvp())/mul", ("", "")),
    ("Mult/jvp()/dot_general", ("Mult", "fwd")),
    ("reduce_sum", ("", "")),
])
def test_scope_of(label, want):
    assert hp.scope_of(label) == want


def test_group_key_meets_forward_and_backward_of_a_layer():
    f = hp._group_key("jvp(LM.blocks.l0.attn.q_proj/Mult)/dot_general", "x")
    b = hp._group_key(
        "transpose(jvp(LM.blocks.l0.attn.q_proj/AddBias))/reduce_sum", "x")
    assert f == "LM.blocks.l0.attn.q_proj fwd"
    assert b == "LM.blocks.l0.attn.q_proj bwd"
    assert hp._group_key("opt/Adam/LM.embed.W/mul", "x") == "opt/Adam"
    assert hp._group_key("opt/guard/reduce_and", "x") == "opt/guard"
    assert hp._group_key("copy.3", "copy") == "copy"


# -- a lowered training step --------------------------------------------------
def test_lm_step_places_every_instruction_that_carries_metadata():
    m, x, y = _lm()
    rows = _labelled(m.step_hlo_text(x, y))
    assert len(rows) > 200
    unplaced = [label for label, scope, _ in rows if not scope]
    assert not unplaced, unplaced[:5]
    # no backward (or forward) instruction with the path empty
    assert not [r for r in rows if "jvp()" in r[0]]
    bwd = {scope for _, scope, d in rows if d == "bwd"}
    fwd = {scope for _, scope, d in rows if d == "fwd"}
    # every backward instruction carries a forward's layer path (the
    # compiler may have fused an op's forward into its neighbour's), and
    # the two blocks read different paths in both directions

    def layers(scopes):
        return {s.rsplit("/", 1)[0] for s in scopes}

    assert layers(bwd) <= layers(fwd), layers(bwd) - layers(fwd)
    for want in ("TransformerLM.blocks.l0.attn.q_proj/Mult",
                 "TransformerLM.blocks.l1.attn.q_proj/Mult",
                 "TransformerLM.blocks.l0.attn/Attention",
                 "TransformerLM.blocks.l1.fc2/Mult",
                 "TransformerLM.ln_f/LayerNorm", "TransformerLM/Mult"):
        assert want in fwd and want in bwd, want
    assert "TransformerLM/SoftMaxCrossEntropy" in fwd
    assert "TransformerLM/SoftMaxCrossEntropy" in bwd


def test_lm_step_optimizer_sits_under_opt_by_parameter():
    m, x, y = _lm()
    rows = _labelled(m.step_hlo_text(x, y))
    under_opt = {scope for _, scope, d in rows if d == ""}
    assert under_opt == {f"opt/Adam/{name}" for name in m.get_params()}
    # Adam's own arithmetic is nowhere else
    for label, scope, _ in rows:
        if label.rsplit("/", 1)[-1] in ("sqrt", "pow", "rsqrt"):
            assert scope.startswith("opt/") or "LayerNorm" in scope \
                or "Gelu" in scope or "Attention" in scope, label


def test_resnet_step_tells_stage_block_and_batchnorm_direction():
    m, x, y = _resnet()
    text = m.step_hlo_text(x, y)
    assert not [label for label, scope, _ in _labelled(text) if not scope]
    # through the map: a fusion the compiler made takes its scope from
    # what it fused (the CPU's convolutions come so)
    got = {(v["scope"], v["dir"])
           for v in hp.scope_map(text)["instructions"].values()}
    for layer in ("ResNet.bn1", "ResNet.layer1.l0.bn1", "ResNet.layer1.l1.bn2",
                  "ResNet.layer2.l0.downsample.bn"):
        assert (f"{layer}/_BatchNorm2d", "fwd") in got, layer
        assert (f"{layer}/_BatchNorm2d", "bwd") in got, layer
    assert ("ResNet.layer1.l0.conv1/_Conv2d", "bwd") in got
    assert ("ResNet.layer1.l1.conv1/_Conv2d", "bwd") in got
    assert ("ResNet/SoftMaxCrossEntropy", "bwd") in got
    assert {s for s, d in got if s and d == ""} == {
        f"opt/SGD/{name}" for name in m.get_params()}


@pytest.mark.parametrize("variant, glue", [
    ("clip", {"opt/clip"}),
    ("guard", {"opt/guard", "opt/loss_scale", "opt/clip"}),
    ("accum", {"opt/accum"}),
    ("remat_policy", set()),
    ("remat_ops", set()),
])
def test_step_glue_each_under_a_scope_of_its_own(variant, glue):
    kw = {}
    if variant == "guard":
        device.set_step_guard(True)
        device.set_loss_scaling(init_scale=8.0)
    elif variant == "accum":
        kw["grad_accum"] = 2
    elif variant == "remat_policy":
        device.set_remat_policy("dots_saveable")
    elif variant == "remat_ops":
        autograd.set_remat(True)
    m, x, y = _lm(clip=variant in ("clip", "guard"), **kw)
    rows = _labelled(m.step_hlo_text(x, y))
    assert not [label for label, scope, _ in rows if not scope]
    scopes = {scope for _, scope, _ in rows}
    assert glue <= scopes, glue - scopes
    updates = {s for s in scopes if s.startswith("opt/Adam/")}
    assert updates == {f"opt/Adam/{name}" for name in m.get_params()}
    assert "TransformerLM.blocks.l1.attn/Attention" in {
        scope for _, scope, d in rows if d == "bwd"}


def test_eager_dispatch_enters_no_scope():
    autograd.training = True
    try:
        a = tensor.from_numpy(np.ones((2, 3), np.float32))
        a.requires_grad = True
        op = autograd.Mult()
        op(a, tensor.from_numpy(np.ones((3, 2), np.float32)))
        assert op._scope is None and not autograd._layer_path
    finally:
        autograd.training = False


# -- the map and the reduction ------------------------------------------------
def test_scope_map_reports_its_module_and_coverage():
    m, x, y = _lm()
    sm = hp.scope_map(m.step_hlo_text(x, y))
    assert sm["module"] == "jit_step_fn"
    assert sm["scoped"] + sm["unscoped"] == len(sm["instructions"])
    assert sm["scoped"] > 10 * sm["unscoped"] > 0
    # what carries no scope is the compiler's own
    assert {v["opcode"] for v in sm["instructions"].values()
            if not v["scope"]} <= {"copy", "fusion", "bitcast-convert"}
    ent = next(v for v in sm["instructions"].values()
               if v["scope"] == "TransformerLM/Mult" and v["dir"] == "fwd")
    assert ent["shape"].startswith("f32[")


_MAP = {"module": "jit_step_fn", "scoped": 4, "unscoped": 1, "instructions": {
    "fusion.1": {"shape": "f32[8,4]", "opcode": "fusion",
                 "scope": "LM.l0/Mult", "dir": "fwd"},
    "fusion.2": {"shape": "f32[8,4]", "opcode": "fusion",
                 "scope": "LM.l0/Mult", "dir": "bwd"},
    "while.3": {"shape": None, "opcode": "while",
                "scope": "opt/accum", "dir": ""},
    "fusion.4": {"shape": "f32[4]", "opcode": "fusion",
                 "scope": "opt/Adam/LM.l0.W", "dir": ""},
    "copy.5": {"shape": "f32[4]", "opcode": "copy", "scope": "", "dir": ""},
}}


def _events(t):
    return [
        ("%fusion.1 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %p), kind=kLoop",
         t + 0, t + 10),
        ("%while.3 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t), "
         "condition=%c, body=%b", t + 10, t + 50),
        ("%fusion.2 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %q), kind=kLoop",
         t + 12, t + 30),          # inside the while
        ("%fusion.4 = f32[4]{0} fusion(f32[4]{0} %g), kind=kLoop",
         t + 30, t + 45),          # inside the while
        ("%copy.5 = f32[4]{0} copy(f32[4]{0} %w)", t + 50, t + 53),
        # a name of the map under another shape: not this program's
        ("%fusion.1 = f32[9,9]{1,0} fusion(f32[9,9]{1,0} %p), kind=kLoop",
         t + 60, t + 64),
        ("%convert.9 = bf16[4]{0} convert(f32[4]{0} %w)", t + 64, t + 66),
    ]


def test_scope_times_adds_up_to_the_events_self_time():
    red = hp.scope_times(_events(100), _MAP)
    rows = {(r["scope"], r["dir"]): r["time"] for r in red["rows"]}
    assert rows == {("LM.l0/Mult", "fwd"): 10, ("LM.l0/Mult", "bwd"): 18,
                    ("opt/Adam/LM.l0.W", ""): 15,
                    ("opt/accum", ""): 40 - 18 - 15}
    assert red["unplaced"] == {"not in map": 4 + 2, "no scope": 3}
    assert red["unplaced_by_opcode"] == {"fusion": 4, "copy": 3, "convert": 2}
    assert red["total"] == 10 + 40 + 3 + 4 + 2
    assert sum(rows.values()) + sum(red["unplaced"].values()) == red["total"]
    assert (red["matched"], red["unmatched"]) == (5, 2)
    assert [r["scope"] for r in red["rows"]][0] == "LM.l0/Mult"  # heaviest


def test_scope_times_keeps_to_its_modules_events():
    events = _events(100) + _events(1000)
    modules = [("jit_step_fn(7)", 100, 170), ("jit_forward(9)", 1000, 1070)]
    red = hp.scope_times(events, _MAP, modules)
    once = hp.scope_times(_events(100), _MAP)
    assert red["total"] == once["total"]
    assert red["elsewhere"] == once["total"]
    assert red["rows"] == once["rows"]
    nothing = hp.scope_times(events, _MAP, [("jit_other(1)", 0, 2000)])
    assert nothing["total"] == 0 and not nothing["rows"]
    assert nothing["elsewhere"] == 2 * once["total"]


def test_the_compile_caches_settings_keep_the_scopes(monkeypatch, tmp_path):
    """`use_compile_cache` puts metadata into the cache's key (a cache
    another tree warmed would hand back that tree's scopes) and cuts a
    location to one frame; `jax_include_full_tracebacks_in_locations`
    off would cut it too, and loses the op_names with it."""
    import jax

    names = ("jax_compilation_cache_include_metadata_in_key",
             "jax_traceback_in_locations_limit",
             "jax_include_full_tracebacks_in_locations")
    saved = {k: getattr(jax.config, k) for k in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert device.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        assert jax.config.jax_include_full_tracebacks_in_locations
        m, x, y = _lm()
        sm = hp.scope_map(m.step_hlo_text(x, y))
        assert sm["scoped"] > 10 * sm["unscoped"]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


# -- the process's step programs ----------------------------------------------
def test_step_programs_outlive_the_model():
    hp._STEP_PROGRAMS.clear()

    def train():
        m, x, y = _lm()
        m(x, y)
        m(x, y)

    train()
    gc.collect()
    progs = hp.step_programs()
    assert [name for name, _ in progs] == ["jit_step_fn"]
    sm = hp.scope_map(progs[0][1])
    assert sm["scoped"] > 200
    assert any(v["scope"] == "opt/Adam/TransformerLM.embed.W"
               for v in sm["instructions"].values())


def test_step_programs_keeps_the_newest_few():
    hp._STEP_PROGRAMS.clear()
    keep = hp._KEEP_PROGRAMS

    class Step:
        pass

    steps = [Step() for _ in range(keep + 3)]
    for i, s in enumerate(steps):
        hp.note_step_program(s, i)
    assert list(hp._STEP_PROGRAMS.values()) == list(range(3, keep + 3))
    hp.note_step_program(steps[5], "again")     # a step's newest replaces
    assert list(hp._STEP_PROGRAMS.values())[-1] == "again"
    assert len(hp._STEP_PROGRAMS) == keep
    hp._STEP_PROGRAMS.clear()


def test_graph_table_groups_by_layer_and_says_estimated():
    m, x, y = _lm()
    rows = hp.profile_hlo(m.step_hlo_text(x, y))
    table = hp.format_table(rows, measured_step_s=0.01, top=400)
    lines = table.splitlines()
    assert "measured step" in lines[0]
    body = lines[1:]
    assert all("estimated" in ln for ln in body)
    ops = [ln.split("OP = ")[1].split("  ")[0].strip() for ln in body]
    for want in ("TransformerLM.blocks.l0.attn.q_proj fwd",
                 "TransformerLM.blocks.l0.attn.q_proj bwd",
                 "TransformerLM.blocks.l1.fc1 bwd", "opt/Adam"):
        assert want in ops, (want, ops[:8])
    assert len(ops) == len(set(ops))
