"""Microbatched gradient accumulation (ISSUE 4).

The contract under test: `device.set_grad_accum(n)` /
`Model.compile(grad_accum=n)` turns one train step into n microbatch
forward/backward passes with fp32 gradient accumulation and ONE
optimizer apply — compiled as a `lax.scan` inside the graph-mode
program, looped with a single fused apply in eager mode, and run
under `shard_map` with exactly one post-scan all-reduce on a pure-DP
mesh.

Bit-identity strategy: most tests feed DYADIC data (inputs, targets,
and params are small multiples of powers of two, lr/momentum are
powers of two) so every product and partial sum in one train step is
exactly representable in fp32 — float addition is then associative in
fact, and "accumulated == monolithic" holds to the BIT regardless of
reduction order, XLA fusion, or device count. Realistic-data tests
cover the same paths with tight tolerances (fp32 summation order is
the only degree of freedom).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import (
    autograd,
    data as data_mod,
    device,
    layer,
    model,
    opt,
    resilience,
    stats,
    tensor,
)
from singa_tpu.parallel import create_mesh


@pytest.fixture(autouse=True)
def _clean_accum():
    """grad_accum / guard / scaler knobs are process-global: reset
    around every test."""
    stats.reset_cache_stats()
    yield
    stats.configure(grad_accum=1, step_guard=False, loss_scaling=None)
    resilience.reset_state()


class MSEMLP(model.Model):
    """Regression MLP: Linear/ReLU/mse only — every op is exact on
    dyadic values (softmax would immediately leave the dyadic grid)."""

    def __init__(self):
        super().__init__()
        self.fc1 = layer.Linear(16)
        self.relu = layer.ReLU()
        self.fc2 = layer.Linear(4)

    def forward(self, x):
        return self.fc2(self.relu(self.fc1(x)))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = autograd.mse_loss(out, y)
        self._optimizer.backward_and_update(loss)
        return out, loss


class SoftmaxMLP(model.Model):
    def __init__(self):
        super().__init__()
        self.fc1 = layer.Linear(16)
        self.relu = layer.ReLU()
        self.fc2 = layer.Linear(3)

    def forward(self, x):
        return self.fc2(self.relu(self.fc1(x)))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = autograd.softmax_cross_entropy(out, y)
        self._optimizer.backward_and_update(loss)
        return out, loss


def _dyadic(rs, shape, scale=0.5):
    return (rs.randint(-2, 3, shape) * scale).astype(np.float32)


_RS = np.random.RandomState(0)
_X = _dyadic(_RS, (32, 8), 0.5)
_Y = _dyadic(_RS, (32, 4), 0.5)


def _build_mse(grad_accum=None, use_graph=True, mesh=None, x=_X, y=_Y,
               slot_dtype=None, lr=0.25):
    m = MSEMLP()
    optimizer = opt.SGD(lr=lr, momentum=0.5)
    if slot_dtype:
        optimizer.set_slot_dtype(slot_dtype)
    m.set_optimizer(optimizer)
    tx, ty = tensor.from_numpy(x), tensor.from_numpy(y)
    m.compile([tx], is_train=True, use_graph=use_graph, mesh=mesh,
              grad_accum=grad_accum)
    prs = np.random.RandomState(42)
    for _, p in sorted(m.get_params().items()):
        p.data = jnp.asarray(_dyadic(prs, p.data.shape, 0.5))
    return m, tx, ty


def _params_np(m):
    return {k: np.asarray(v.to_numpy())
            for k, v in m.get_params().items()}


def _slots_np(m):
    """Optimizer slots keyed by param NAME (id-keyed dict insertion
    order differs between the eager and graph slot-creation paths)."""
    name_of = {id(p): k for k, p in m.get_params().items()}
    return {name_of[pid]: {n: np.asarray(a, np.float32)
                           for n, a in st.items()}
            for pid, st in m._optimizer.states.items()
            if pid in name_of}


def _assert_trees_equal(a, b):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=str(k))


# ---------------------------------------------------------------------------
# data.microbatches
# ---------------------------------------------------------------------------
class TestMicrobatches:
    def test_array_split(self):
        x = np.arange(12).reshape(6, 2)
        parts = data_mod.microbatches(x, 3)
        assert len(parts) == 3
        np.testing.assert_array_equal(parts[1], x[2:4])

    def test_pytree_split(self):
        x = np.arange(8).reshape(8, 1)
        y = np.arange(8)
        parts = data_mod.microbatches((x, {"y": y}), 4)
        assert len(parts) == 4
        np.testing.assert_array_equal(parts[2][0], x[4:6])
        np.testing.assert_array_equal(parts[2][1]["y"], y[4:6])

    def test_tensor_leaves_stay_tensors(self):
        tx = tensor.from_numpy(_X)
        parts = data_mod.microbatches([tx], 4)
        assert all(hasattr(p[0], "device") for p in parts)
        np.testing.assert_array_equal(
            np.asarray(parts[3][0].data), _X[24:32])

    def test_indivisible_is_loud(self):
        with pytest.raises(ValueError, match="not divisible"):
            data_mod.microbatches(np.zeros((7, 2)), 2)

    def test_mismatched_leaves_are_loud(self):
        with pytest.raises(ValueError, match="disagree"):
            data_mod.microbatches((np.zeros((8, 2)), np.zeros(6)), 2)

    def test_pad_repeats_tail(self):
        x = np.arange(7)
        parts = data_mod.microbatches(x, 2, pad=True)
        assert len(parts) == 2 and len(parts[1]) == 4
        assert parts[1][-1] == x[-1]  # repeated final sample

    def test_n1_is_identity(self):
        x = np.arange(6)
        (part,) = data_mod.microbatches(x, 1)
        np.testing.assert_array_equal(part, x)


# ---------------------------------------------------------------------------
# bit-identity: accum-n step == monolithic big-batch step (fp32, CPU)
# ---------------------------------------------------------------------------
class TestBitIdentity:
    @pytest.mark.parametrize("use_graph", [True, False])
    def test_accum4_step_equals_monolithic(self, use_graph):
        """The acceptance bit: one accum-4 step — graph (scan-fused)
        AND eager (captured microbatch loop) — leaves params, slots,
        outputs, and the loss bit-identical to the monolithic
        batch-32 step."""
        m1, tx, ty = _build_mse(None, use_graph=True)
        out1, l1 = m1(tx, ty)
        m2, tx2, ty2 = _build_mse(4, use_graph=use_graph)
        out2, l2 = m2(tx2, ty2)
        np.testing.assert_array_equal(np.asarray(l1.data),
                                      np.asarray(l2.data))
        np.testing.assert_array_equal(np.asarray(out1.data),
                                      np.asarray(out2.data))
        _assert_trees_equal(_params_np(m1), _params_np(m2))
        s1, s2 = _slots_np(m1), _slots_np(m2)
        assert s1.keys() == s2.keys()
        for k in s1:
            for n in s1[k]:
                np.testing.assert_array_equal(s1[k][n], s2[k][n],
                                              err_msg=f"{k}/{n}")

    def test_eager_and_graph_accum_identical_over_steps(self):
        """The two accumulation drivers share the fp32 sum order and
        the mean division, so the PARAMETERS stay bit-identical after
        every step at ANY magnitude (no dyadic construction needed).
        The reported loss is held to the ulps its own reduction may
        cost: each microbatch's mean-square is summed inside the
        scan-fused program in the order XLA's fusion picks and by a
        standalone reduce in the eager loop, so on non-dyadic data a
        microbatch loss may round one ulp apart (it does, at step 2
        under jax 0.9), and the mean of n = 4 of them by no more than
        n ulps. Nothing of that reaches the gradients."""
        rs = np.random.RandomState(3)
        x = rs.randn(32, 8).astype(np.float32)
        y = rs.randn(32, 4).astype(np.float32)
        mg, txg, tyg = _build_mse(4, use_graph=True, x=x, y=y, lr=0.05)
        me, txe, tye = _build_mse(4, use_graph=False, x=x, y=y,
                                  lr=0.05)
        for _ in range(3):
            _, lg = mg(txg, tyg)
            _, le = me(txe, tye)
            lg, le = np.asarray(lg.data), np.asarray(le.data)
            np.testing.assert_array_max_ulp(lg, le, maxulp=4)
            _assert_trees_equal(_params_np(mg), _params_np(me))
        # what the bound still refuses: a loss off by 1e-4 (a hundred
        # ulps at this magnitude)
        with pytest.raises(AssertionError):
            np.testing.assert_array_max_ulp(lg, le + np.float32(1e-4),
                                            maxulp=4)

    def test_accum_close_to_monolithic_on_softmax_model(self):
        """Realistic config (softmax CE, randn data): accumulation
        only changes fp32 summation order — multi-step trajectories
        stay within tight tolerance of the monolithic run."""
        rs = np.random.RandomState(5)
        x = rs.randn(32, 8).astype(np.float32)
        yi = rs.randint(0, 3, 32).astype(np.int32)

        def build(ga):
            m = SoftmaxMLP()
            m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
            tx, ty = tensor.from_numpy(x), tensor.from_numpy(yi)
            m.compile([tx], is_train=True, use_graph=True,
                      grad_accum=ga)
            prs = np.random.RandomState(11)
            for _, p in sorted(m.get_params().items()):
                p.data = jnp.asarray(
                    prs.randn(*p.data.shape).astype(np.float32) * 0.1)
            return m, tx, ty

        m1, tx1, ty1 = build(None)
        m2, tx2, ty2 = build(4)
        for _ in range(5):
            _, l1 = m1(tx1, ty1)
            _, l2 = m2(tx2, ty2)
        np.testing.assert_allclose(float(l1.to_numpy()),
                                   float(l2.to_numpy()), rtol=1e-5)
        p1, p2 = _params_np(m1), _params_np(m2)
        for k in p1:
            np.testing.assert_allclose(p1[k], p2[k], atol=2e-5,
                                       err_msg=k)

    def test_process_knob_applies_and_compile_arg_overrides(self):
        device.set_grad_accum(4)
        m, tx, ty = _build_mse(None, use_graph=True)
        m(tx, ty)
        assert m._jit_step._accum_built == 4
        # compile(grad_accum=1) pins accumulation OFF despite the knob
        m2, tx2, ty2 = _build_mse(1, use_graph=True)
        m2(tx2, ty2)
        assert m2._jit_step._accum_built == 1


# ---------------------------------------------------------------------------
# interplay matrix: guard skip / scaler unscale-once / bf16 slots /
# donation
# ---------------------------------------------------------------------------
class TestInterplay:
    @pytest.mark.parametrize("use_graph", [True, False])
    def test_guard_skips_whole_accumulated_step(self, use_graph):
        """A NaN in ONE microbatch poisons the accumulated grads; the
        guard's single finite check skips the WHOLE accumulated step
        (params/slots bit-identical, exactly one skip counted)."""
        device.set_step_guard(True)
        m, tx, ty = _build_mse(4, use_graph=use_graph, lr=0.125)
        for _ in range(2):
            m(tx, ty)
        before = stats.cache_stats()["resilience"]
        bp, bs = _params_np(m), _slots_np(m)
        xb = _X.copy()
        xb[9, 0] = np.nan  # lands in microbatch 1 of 4
        m(tensor.from_numpy(xb), ty)
        after = stats.cache_stats()["resilience"]
        _assert_trees_equal(bp, _params_np(m))
        for pid in bs:
            for n in bs[pid]:
                np.testing.assert_array_equal(
                    bs[pid][n], _slots_np(m)[pid][n])
        assert after["steps_skipped"] == before["steps_skipped"] + 1
        # a clean step still applies
        m(tx, ty)
        assert stats.cache_stats()["resilience"]["steps_applied"] == \
            after["steps_applied"] + 1

    @pytest.mark.parametrize("use_graph", [True, False])
    def test_scaler_unscales_accumulated_grads_exactly(self,
                                                       use_graph):
        """Power-of-two loss scaling must round-trip the accumulation
        bit-exactly: the backward seed is scaled per microbatch, the
        fp32 accumulator carries the scale linearly, and the single
        unscale at apply recovers the scaler-off step to the bit —
        at any data magnitude (exponent shifts commute with fp32
        adds). Guard counters advance once per ACCUMULATED step."""
        rs = np.random.RandomState(9)
        x = rs.randn(32, 8).astype(np.float32)
        y = rs.randn(32, 4).astype(np.float32)
        m_off, tx0, ty0 = _build_mse(4, use_graph=use_graph, x=x, y=y,
                                     lr=0.05)
        for _ in range(3):
            m_off(tx0, ty0)
        device.set_loss_scaling(init_scale=2.0 ** 10,
                                growth_interval=0)
        m_on, tx1, ty1 = _build_mse(4, use_graph=use_graph, x=x, y=y,
                                    lr=0.05)
        for _ in range(3):
            m_on(tx1, ty1)
        _assert_trees_equal(_params_np(m_off), _params_np(m_on))
        res = stats.cache_stats()["resilience"]
        assert res["steps_applied"] == 3  # one per accumulated step
        assert res["loss_scale"] == 2.0 ** 10

    def test_bf16_slots_quantize_once_at_final_apply(self):
        """bf16 slot storage composes: the accum step quantizes the
        slot exactly once (at the single apply), so it matches the
        monolithic bf16-slot step bit-for-bit on dyadic data — and
        the stored slots really are bf16."""
        m1, tx1, ty1 = _build_mse(None, slot_dtype="bfloat16")
        m1(tx1, ty1)
        m2, tx2, ty2 = _build_mse(4, slot_dtype="bfloat16")
        m2(tx2, ty2)
        _assert_trees_equal(_params_np(m1), _params_np(m2))
        for st in m2._optimizer.states.values():
            for arr in st.values():
                assert jnp.asarray(arr).dtype == jnp.bfloat16

    def test_donation_toggle_changes_nothing(self):
        device.set_buffer_donation(False)
        try:
            m1, tx1, ty1 = _build_mse(4)
            m1(tx1, ty1)
        finally:
            device.set_buffer_donation(True)
        m2, tx2, ty2 = _build_mse(4)
        m2(tx2, ty2)
        _assert_trees_equal(_params_np(m1), _params_np(m2))

    def test_distopt_accumulation_is_loud(self):
        optimizer = opt.DistOpt(opt.SGD(lr=0.1), world_size=1)
        with pytest.raises(RuntimeError, match="mesh"):
            optimizer._accum_begin()


# ---------------------------------------------------------------------------
# compiled-program properties: microbatch live range, observability,
# validation
# ---------------------------------------------------------------------------
class TestProgram:
    def test_grad_live_range_stays_at_microbatch_size(self):
        """The scan body computes on [mb]-sized activations/gradients;
        the full-batch hidden activation must not exist anywhere in
        the n=4 program (that's the HBM headroom the feature buys)."""
        rs = np.random.RandomState(1)
        x = rs.randn(64, 8).astype(np.float32)
        y = rs.randn(64, 4).astype(np.float32)
        m, tx, ty = _build_mse(4, x=x, y=y)
        hlo = m.step_hlo_text(tx, ty)
        # hidden layer is 16-wide: microbatch activations [16,16]
        # present, full-batch [64,16] absent
        assert "f32[16,16]" in hlo
        assert "f32[64,16]" not in hlo

    def test_monolithic_program_has_full_batch_live(self):
        """Control for the test above: without accum the full-batch
        hidden activation IS in the program."""
        rs = np.random.RandomState(1)
        x = rs.randn(64, 8).astype(np.float32)
        y = rs.randn(64, 4).astype(np.float32)
        m, tx, ty = _build_mse(None, x=x, y=y)
        assert "f32[64,16]" in m.step_hlo_text(tx, ty)

    def test_cache_stats_accum_geometry_and_counter(self):
        m, tx, ty = _build_mse(4, use_graph=True)
        m(tx, ty)
        m(tx, ty)
        snap = stats.cache_stats()["accum"]
        assert snap["n"] == 4
        assert snap["microbatch"] == 8
        assert snap["effective_batch"] == 32
        assert snap["accum_steps"] == 2
        assert snap["configured_n"] == 1  # compile() arg, not knob

    @pytest.mark.parametrize("use_graph", [True, False])
    def test_train_steps_counts_microbatches_in_both_modes(
            self, use_graph):
        """train_steps means 'train_one_batch invocations' whichever
        mode trained: an accum-4 step advances it by 4 in eager AND
        graph mode (graph trace-time invocations excluded by counting
        after warmup). Uses the DEFAULT train_one_batch — models that
        override it wholesale opt out of eager counting by the
        documented contract."""

        class DefaultMLP(model.Model):
            def __init__(self):
                super().__init__()
                self.fc1 = layer.Linear(16)
                self.relu = layer.ReLU()
                self.fc2 = layer.Linear(3)

            def forward(self, x):
                return self.fc2(self.relu(self.fc1(x)))

        rs = np.random.RandomState(4)
        x = rs.randn(32, 8).astype(np.float32)
        yi = rs.randint(0, 3, 32).astype(np.int32)
        m = DefaultMLP()
        m.set_optimizer(opt.SGD(lr=0.05))
        tx, ty = tensor.from_numpy(x), tensor.from_numpy(yi)
        m.compile([tx], is_train=True, use_graph=use_graph,
                  grad_accum=4)
        m(tx, ty)  # warmup: pays the trace-time invocations
        before = stats.cache_stats()["train_steps"]
        m(tx, ty)
        m(tx, ty)
        assert stats.cache_stats()["train_steps"] == before + 8

    def test_indivisible_batch_is_loud(self):
        rs = np.random.RandomState(2)
        x = rs.randn(30, 8).astype(np.float32)
        y = rs.randn(30, 4).astype(np.float32)
        m, tx, ty = _build_mse(4, x=x, y=y)
        with pytest.raises(ValueError, match="divisible"):
            m(tx, ty)

    def test_eager_indivisible_batch_is_loud(self):
        rs = np.random.RandomState(2)
        x = rs.randn(30, 8).astype(np.float32)
        y = rs.randn(30, 4).astype(np.float32)
        m, tx, ty = _build_mse(4, use_graph=False, x=x, y=y)
        with pytest.raises(ValueError, match="divisible"):
            m(tx, ty)


# ---------------------------------------------------------------------------
# mesh: one post-scan reduction, rank-identical math
# ---------------------------------------------------------------------------
def _hlo_computations(hlo):
    comps, cur = {}, None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and "{" in line:
            cur = line.split("{")[0].strip()
            comps[cur] = []
        if cur is not None:
            comps[cur].append(line)
    return comps


_MX = _dyadic(np.random.RandomState(7), (64, 8), 0.5)
_MY = _dyadic(np.random.RandomState(8), (64, 4), 0.5)


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def _collectives(lines):
    """(opcode, line) of every collective instruction among HLO text
    `lines`, read by OPCODE (`= <shape> all-reduce(`, or its
    asynchronous `-start(` spelling), never by the instruction's
    name: jax names a psum's all-reduce `%psum.7`."""
    found = []
    for ln in lines:
        m = re.search(r" = .*?\b(%s)(-start)?\(" % "|".join(_COLLECTIVES),
                      ln)
        if m:
            found.append((m.group(1), ln.strip()))
    return found


def _assert_one_allreduce_after_the_scan(hlo):
    """Exactly ONE all-reduce in the program, in the ENTRY computation
    (after the scan), and no collective of any kind in another
    computation (the while body above all)."""
    ars = [ln for op, ln in _collectives(hlo.splitlines())
           if op == "all-reduce"]
    assert len(ars) == 1, (
        f"expected 1 all-reduce, got {len(ars)}:\n" + "\n".join(ars))
    for name, lines in _hlo_computations(hlo).items():
        if name.startswith("ENTRY"):
            continue
        inside = _collectives(lines[1:])
        assert not inside, f"collective inside {name}: {inside}"


class TestMesh:
    def test_single_allreduce_outside_the_scan(self):
        """THE amortization claim: the pure-DP accum-4 program carries
        exactly ONE all-reduce (the flat fp32 grad+loss+state bucket),
        and it lives in the ENTRY computation — after the scan — not
        in the while body. No other collective touches the loop."""
        mesh = create_mesh({"data": 8})
        m, tx, ty = _build_mse(4, mesh=mesh, x=_MX, y=_MY)
        hlo = m.step_hlo_text(tx, ty)
        assert "while(" in hlo, "the accumulation is no loop any more"
        _assert_one_allreduce_after_the_scan(hlo)
        # the guard sees what it guards against: the same program with
        # its all-reduce repeated in, or moved into, the scan's body
        # is refused, and so is one whose reduction was lost
        lines = hlo.splitlines()
        ar = [ln for ln in lines if _collectives([ln])][0]
        body = re.search(r"f32\[[^\n]* while\([^\n]*body=(%[\w.]+)",
                         hlo).group(1)
        head = [ln for ln in lines if ln.startswith(body + " ")
                and ln.endswith("{")][0]
        second = hlo.replace(head, head + "\n" + ar, 1)
        with pytest.raises(AssertionError, match="all-reduce, got 2"):
            _assert_one_allreduce_after_the_scan(second)
        moved = hlo.replace(ar, "", 1).replace(head, head + "\n" + ar, 1)
        with pytest.raises(AssertionError, match="collective inside"):
            _assert_one_allreduce_after_the_scan(moved)
        with pytest.raises(AssertionError, match="all-reduce, got 0"):
            _assert_one_allreduce_after_the_scan(hlo.replace(ar, "", 1))

    def test_mesh_accum_matches_single_device_monolithic(self):
        """Dyadic data again: the mesh accum-4 step (8 devices, local
        scan, one psum) is bit-identical to the single-device
        monolithic batch-64 step — partition into devices and
        microbatches changes nothing when the arithmetic is exact."""
        m1, tx1, ty1 = _build_mse(None, x=_MX, y=_MY)
        out1, l1 = m1(tx1, ty1)
        mesh = create_mesh({"data": 8})
        m2, tx2, ty2 = _build_mse(4, mesh=mesh, x=_MX, y=_MY)
        out2, l2 = m2(tx2, ty2)
        np.testing.assert_array_equal(np.asarray(l1.data),
                                      np.asarray(l2.data))
        np.testing.assert_array_equal(np.asarray(out1.data),
                                      np.asarray(out2.data))
        _assert_trees_equal(_params_np(m1), _params_np(m2))

    def test_mesh_accum_guard_skip_is_global(self):
        """The finite bit is computed from the post-psum GLOBAL grads:
        a NaN local to one device's shard skips the step everywhere,
        params stay bit-identical, one skip counted."""
        device.set_step_guard(True)
        mesh = create_mesh({"data": 8})
        m, tx, ty = _build_mse(4, mesh=mesh, x=_MX, y=_MY, lr=0.125)
        m(tx, ty)
        before = stats.cache_stats()["resilience"]
        bp = _params_np(m)
        xb = _MX.copy()
        xb[3, 0] = np.nan  # one device's shard only
        m(tensor.from_numpy(xb), ty)
        _assert_trees_equal(bp, _params_np(m))
        after = stats.cache_stats()["resilience"]
        assert after["steps_skipped"] == before["steps_skipped"] + 1

    def test_int_output_leaf_takes_global_fallback(self):
        """A non-batch INTEGER output (e.g. a correct-prediction
        count) cannot be psum-averaged, and reporting one shard's
        local value as global would be silent corruption — the
        shard_map path must detect it at discovery and fall back to
        the GSPMD scan, whose outputs are globally computed: the mesh
        count equals the single-device count."""

        class CountingMLP(MSEMLP):
            def train_one_batch(self, x, y):
                out = self.forward(x)
                loss = autograd.mse_loss(out, y)
                self._optimizer.backward_and_update(loss)
                count = (out.data > 0).sum().astype(jnp.int32)
                return out, loss, count

        def build(mesh):
            m = CountingMLP()
            m.set_optimizer(opt.SGD(lr=0.25, momentum=0.5))
            tx, ty = tensor.from_numpy(_MX), tensor.from_numpy(_MY)
            m.compile([tx], is_train=True, use_graph=True, mesh=mesh,
                      grad_accum=4)
            prs = np.random.RandomState(42)
            for _, p in sorted(m.get_params().items()):
                p.data = jnp.asarray(_dyadic(prs, p.data.shape, 0.5))
            return m, tx, ty

        m1, tx1, ty1 = build(None)
        _, _, c1 = m1(tx1, ty1)
        m2, tx2, ty2 = build(create_mesh({"data": 8}))
        _, _, c2 = m2(tx2, ty2)
        assert int(np.asarray(c1.data)) == int(np.asarray(c2.data))
        _assert_trees_equal(_params_np(m1), _params_np(m2))

    def test_tp_mesh_falls_back_and_still_matches(self):
        """Non-pure-DP (a 'model' axis with sharded params) takes the
        GSPMD-scan fallback: reductions stay in the loop, but the math
        is the same — bit-identical on dyadic data."""
        m1, tx1, ty1 = _build_mse(None, x=_MX, y=_MY)
        m1(tx1, ty1)
        mesh = create_mesh({"data": 4, "model": 2})
        m2, tx2, ty2 = _build_mse(4, mesh=mesh, x=_MX, y=_MY)
        m2(tx2, ty2)
        _assert_trees_equal(_params_np(m1), _params_np(m2))
