"""The decode tier's contract, one test a point, over every form it
serves (ISSUE 29).

`serve.py` drives a model through `DecodeLM`'s three programs
(`decode_step`, `decode_scan` and its k = 1 case, the token program of
a single greedy step, `prefill_slab`) and the slab's geometry
(`new_slab / grow_slab / slab_dims / slab_bytes`, `export_slab_rows /
import_slab_rows`) and never looks inside. What it relies on is held
here for each form alike, at toy widths on the CPU:

  lm-layernorm-tied   `TransformerLM`, float32, LayerNorm, tied head
                      (GPT-2's form: both GPT-2 serving cells)
  lm-rmsnorm-untied   `TransformerLM`, RMSNorm, a head of its own
  lm-int8             `TransformerLM` under `_decode_params_quant()`:
                      int8 weights and an int8 slab with its scales
  hybrid-dense        `HybridWindowMoELM`, every held expert over
                      every row (the decode step of the mimo cell)
  hybrid-sorted       the same model, assignments sorted by expert
                      (its prefill path)
  shortconv           `ShortConvMoELM`: convolution states beside
                      contexts, every expert held (the lfm2 cell)
  chunked             `ChunkedAttnLM`: window buffers of 8 positions
                      beside 2-to-1 chunk summaries, which are what
                      climbs the ladder (the evabyte cell); the rows
                      cross a block boundary inside every test
  blocksparse         `BlockSparseMoELM`: contexts beside a pooled key
                      a block of 4 positions, BOTH climbing the ladder,
                      the pooled keys starting at the dtype's lowest
                      value, not zero (the minimax cell); a query reads
                      block 0, its own and the best-scoring one, so the
                      rows past 12 positions drop blocks

A form that cannot meet a point says so as a skipped case, with the
model's own reason. The next decode-tier model adds one row to FORMS.
"""
import numpy as np
import pytest

from singa_tpu import device, serve, stats, tensor
from singa_tpu.models.block_sparse_moe import BlockSparseMoELM
from singa_tpu.models.chunked_attn import ChunkedAttnLM
from singa_tpu.models.hybrid_moe import HybridWindowMoELM
from singa_tpu.models.shortconv_moe import ShortConvMoELM
from singa_tpu.models.transformer import TransformerLM

V, D = 64, 32
MAXLEN = 64
WINDOW = 4
FORMS = ["lm-layernorm-tied", "lm-rmsnorm-untied", "lm-int8",
         "hybrid-dense", "hybrid-sorted", "shortconv", "chunked",
         "blocksparse"]


@pytest.fixture(autouse=True)
def _highest():
    before = tensor.get_matmul_precision()
    tensor.set_matmul_precision("highest")
    yield
    tensor.set_matmul_precision(before)


class Form:
    """A model, the parameter tree its programs take, and what differs
    between the forms: where a slab leaf keeps its slots, how far the
    cached path may sit from the eval forward, how `eps` is set."""

    def __init__(self, name):
        dev = device.get_default_device()
        dev.SetRandSeed(11)
        self.name = name
        # the models drawn on the device (`DrawnDecodeLM`): a slab of
        # more than one kind of entry, slots first in every leaf
        self.hybrid = name.startswith(("hybrid", "shortconv", "chunked",
                                       "blocksparse"))
        self.int8 = name == "lm-int8"
        # what does not climb the ladder, what does, and how many
        # positions an entry of what does stands for
        self.fixed_kind = {"hybrid": "ring", "shortc": "state",
                           "chunke": "window"}.get(name[:6])
        self.grows, self.per = (("summary", 2) if name == "chunked"
                                else ("context", 1))
        # every kind the slab states, and the positions an entry of
        # each kind that climbs the ladder stands for
        self.kinds = {self.fixed_kind or "ring", self.grows}
        self.pers = {self.per}
        if name == "blocksparse":
            self.kinds, self.pers = {"context", "blockkey"}, {1, 4}
            # d_model 48, heads of 12: no axis but a rung's is 16 or 32
            m = BlockSparseMoELM(
                V, d_model=48, num_heads=4, kv_heads=2, head_dim=12,
                rotary_dim=4, index_heads=2, index_dim=12, block=4,
                top_blocks=1, local_blocks=1, moe_layers=(0, 1), d_ff=64,
                d_ff_expert=16, d_ff_shared=16, n_experts=8,
                experts_per_token=2, held=(2, 4), max_len=MAXLEN,
                prefill_block=8, prefill_tile=4, init_std=0.3)
        elif name == "chunked":
            # no axis of its slab is a contract rung (16, 32) but the
            # summary list's on the rung twice as long
            m = ChunkedAttnLM(
                V, d_model=48, num_heads=4, head_dim=12, window=2 * WINDOW,
                chunk=2, num_layers=2, d_ff=64, pred_heads=2,
                max_len=MAXLEN, init_std=0.3)
        elif name == "shortconv":
            # d_model 48: the contract's rungs (16, 32) are no axis of
            # a state [slots, 2, d_model]
            m = ShortConvMoELM(
                V, d_model=48, num_heads=4, kv_heads=2, head_dim=12,
                layer_types=("conv", "full_attention", "conv"),
                num_dense_layers=1, d_ff=64, d_ff_expert=16, n_experts=8,
                experts_per_token=2, held=(0, 8), max_len=MAXLEN,
                init_std=0.3)
        elif self.hybrid:
            m = HybridWindowMoELM(
                V, d_model=D, num_heads=4, head_dim=12, v_head_dim=8,
                kv_heads_full=1, kv_heads_window=2, window=WINDOW,
                rotary_dim=4, layer_pattern=(0, 1, 0),
                moe_layers=(0, 1, 1), d_ff=64, d_ff_expert=16,
                n_experts=8, experts_per_token=2, held=(2, 4),
                max_len=MAXLEN, init_std=0.3)
            m.dense_rows = 256 if name == "hybrid-dense" else 0
        else:
            rms = name == "lm-rmsnorm-untied"
            m = TransformerLM(V, d_model=D, num_heads=2, num_layers=2,
                              max_len=MAXLEN, norm="rms" if rms else "layer",
                              tie_embeddings=not rms)
        m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                     device=dev)],
                  is_train=False, use_graph=False)
        m.eval()
        self.m = m
        # a leaf's slots: axis 1 of [2, B, H, D, T] and of the int8
        # scales [2, B, T]; axis 0 of the drawn models' keys, values
        # and states
        self.slot_axis = 0 if self.hybrid else 1

    @property
    def params(self):
        return (self.m._decode_params_quant() if self.int8
                else self.m._decode_params())

    def set_eps(self, eps):
        """Every norm's `eps`; returns what it was."""
        m = self.m
        if self.hybrid:
            was, m.norm_eps = m.norm_eps, eps
            return was
        was = m.ln_f.eps
        for blk in m.blocks._seq:
            blk.ln1.eps = blk.ln2.eps = eps
        m.ln_f.eps = eps
        return was

    def forward(self, ids):
        """Eval-forward logits [S, V] of one prompt, in float32."""
        return self.m.forward(
            tensor.from_numpy(np.asarray(ids, np.int32)[None])
        ).to_numpy()[0]

    def agrees(self, got, want):
        """The cached path against the eval forward: float32 rounding
        through the layers; under int8 every logit within 5 % of the
        largest, and another top-1 only at a near-tie of the
        reference's own."""
        if not self.int8:
            np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
            return
        err = 0.05 * np.abs(want).max()
        assert np.abs(got - want).max() < err
        assert want.max(-1) - want[got.argmax(-1)] <= err


@pytest.fixture(scope="module", params=FORMS)
def form(request):
    return Form(request.param)


def put(a):
    import jax.numpy as jnp

    return jnp.asarray(a)


def ids_of(n, seed):
    return np.random.default_rng(seed).integers(0, V, (n,), dtype=np.int32)


def leaves(slab):
    import jax

    return jax.tree_util.tree_leaves(slab)


def host(slab):
    """The slab's leaves on the host, before a program takes it."""
    return [np.asarray(leaf) for leaf in leaves(slab)]


def slot_of(form, leaf, slot):
    return np.take(leaf, slot, axis=form.slot_axis)


def new_slab(form, slots, seq=16):
    import jax

    return form.m.new_slab(form.params, slots, seq, jax.devices()[0])


def prefill(form, slab, rows, slots=None, bucket=8):
    """rows: 1-d id arrays -> (logits [len(rows), V] on the host, slab)."""
    ids = np.zeros((len(rows), bucket), np.int32)
    for r, row in enumerate(rows):
        ids[r, :len(row)] = row
    n = np.asarray([len(r) for r in rows], np.int32)
    slots = np.arange(len(rows)) if slots is None else slots
    lg, slab = form.m.prefill_slab(
        form.params, slab, put(ids), put(n),
        put(np.asarray(slots, np.int32)))
    return np.asarray(lg), slab


def step(form, slab, tok, pos):
    lg, slab = form.m.decode_step(form.params, slab,
                                  put(np.asarray(tok, np.int32)),
                                  put(np.asarray(pos, np.int32)))
    return np.asarray(lg), slab


PROMPTS = [ids_of(5, 1), ids_of(3, 2), ids_of(7, 3)]
LENS = np.asarray([5, 3, 7], np.int32)


def started(form, seq=16):
    """Three rows prefilled into a fresh slab: (first tokens, the
    positions they go to, slab)."""
    lg, slab = prefill(form, new_slab(form, 3, seq), PROMPTS)
    return lg.argmax(-1).astype(np.int32), LENS.copy(), slab


# -- 1 ---------------------------------------------------------------------
def test_a_block_is_its_steps(form):
    """`decode_scan(k)` is k greedy `decode_step`s: the same tokens
    and the same slab, bit for bit (7 steps: past the hybrid model's
    ring of 4): every leaf of every `TransformerLM` form, the int8
    slab's float32 scale planes with its payload. Only the hybrid
    model's float leaves get a tolerance, the one shown: its last
    layer differs by an ulp in 27 of 576 floats, where the unrolled
    block fuses what a step computes apart."""
    k = 7
    tok, pos, slab = started(form)
    want = []
    for _ in range(k):
        lg, slab = step(form, slab, tok, pos)
        tok, pos = lg.argmax(-1).astype(np.int32), pos + 1
        want.append(tok)
    by_steps = host(slab)
    tok, pos, slab = started(form)
    toks, slab = form.m.decode_scan(form.params, slab, put(tok), put(pos), k)
    assert np.array_equal(np.asarray(toks), np.stack(want))
    for a, b in zip(host(slab), by_steps):
        assert a.dtype == b.dtype
        if form.hybrid and a.dtype != np.int8:
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)
        else:
            assert np.array_equal(a, b)


# -- 2 ---------------------------------------------------------------------
def test_a_step_after_prefill_is_the_forward_at_that_position(form):
    """A cohort prefill reads each row's own last real token, and the
    steps after it through the slab give what the eval forward gives
    at that position of the whole context (4 steps: the rows sit at
    different positions and cross the hybrid model's window of 4)."""
    ctx = [list(p) for p in PROMPTS]
    lg, slab = prefill(form, new_slab(form, 3), PROMPTS)
    pos, seen = LENS.copy(), []
    for _ in range(4):
        seen.append(lg)
        tok = lg.argmax(-1).astype(np.int32)
        for r in range(3):
            ctx[r].append(int(tok[r]))
        lg, slab = step(form, slab, tok, pos)
        pos = pos + 1
    seen.append(lg)
    for r in range(3):
        want = form.forward(ctx[r])           # causal: [len, V] at once
        for s, got in enumerate(seen):
            form.agrees(got[r], want[LENS[r] - 1 + s])


# -- 3 ---------------------------------------------------------------------
def test_a_row_whose_slot_is_out_of_bounds_is_dropped(form):
    """`slots` is traced: one executable a cohort shape serves every
    assignment, and a pad row (slot == the slab's slots) writes
    nothing. Its neighbours in the slab stay bit for bit, the real row
    lands in its slot, within the bucket and no further, and its
    logits are those of the same cohort with the pad row's slot in
    bounds."""
    rung, bucket = 32, 8        # no other axis of these models is 32
    _, slab = prefill(form, new_slab(form, 3, rung),
                      [PROMPTS[0], PROMPTS[2]], slots=[0, 2])
    before = host(slab)
    cohort = [ids_of(6, 4), ids_of(4, 5)]
    lg, slab = prefill(form, slab, cohort, slots=[1, 3])
    after = host(slab)
    # what a slot holds before anything is written: zeros, or the
    # lowest value where a kind keeps a running max
    fresh = host(new_slab(form, 3, rung))
    for a, b, f in zip(before, after, fresh):
        for kept in (0, 2):
            assert np.array_equal(slot_of(form, a, kept),
                                  slot_of(form, b, kept))
        assert np.array_equal(slot_of(form, a, 1), slot_of(form, f, 1))
        assert not np.array_equal(slot_of(form, b, 1), slot_of(form, f, 1))
        if rung in b.shape:                 # a context: no ring, no state
            axis = b.shape.index(rung)
            past = np.arange(bucket, rung)
            assert np.array_equal(np.take(b, past, axis=axis),
                                  np.take(f, past, axis=axis))
    lg_in, _ = prefill(form, new_slab(form, 3, rung), cohort, slots=[1, 2])
    assert np.array_equal(lg, lg_in)


# -- 4 ---------------------------------------------------------------------
def test_exported_rows_import_into_another_slot_bit_for_bit(form):
    """`export_slab_rows` -> `import_slab_rows` into another slot of a
    slab on another rung: the rows export again as they came (payload
    and scales under int8), the slot's neighbours stay untouched, and
    the session goes on there as it would have here."""
    tok, pos, slab = started(form)
    lg, slab = step(form, slab, tok, pos)
    tok, pos = lg.argmax(-1).astype(np.int32), pos + 1
    held = int(pos[2])
    try:
        rows = form.m.export_slab_rows(slab, 2, held)
    except NotImplementedError as e:
        with pytest.raises(NotImplementedError):
            form.m.import_slab_rows(slab, 0, None)
        pytest.skip(str(e))
    other = new_slab(form, 2, seq=32)
    other = form.m.import_slab_rows(other, 1, rows)
    again = form.m.export_slab_rows(other, 1, held)
    as_tuple = (lambda r: r if isinstance(r, tuple) else (r,))
    assert form.int8 == isinstance(rows, tuple)
    # the wire form, whatever the slab's own layout: [L, 2, H, pos, D],
    # and under int8 the PACKED pair with scales [L, 2, pos], a
    # quarter of the float32 rows' bytes and a little
    pay = as_tuple(rows)[0]
    assert pay.shape == (2, 2, 2, held, 16)
    if form.int8:
        assert pay.dtype == np.int8 and rows[1].dtype == np.float32
        assert rows[1].shape == (2, 2, held)
        assert pay.nbytes + rows[1].nbytes < 0.3 * pay.size * 4
    for a, b in zip(as_tuple(rows), as_tuple(again)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    for leaf in host(other):
        assert not slot_of(form, leaf, 0).any()
    here, _ = step(form, slab, tok, pos)
    there, _ = step(form, other, [0, tok[2]], [0, held])
    np.testing.assert_allclose(there[1], here[2], rtol=0, atol=1e-5)
    assert there[1].argmax() == here[2].argmax()


# -- 5 ---------------------------------------------------------------------
def test_slab_bytes_are_the_leaves_and_growth_keeps_what_was_written(form):
    """`slab_bytes` by kind adds up to the leaves' bytes (rings only
    where the model has window layers, states only where it has
    convolution layers); `grow_slab` moves the slab to a longer rung
    with every written position where it was, zeros behind, rings and
    states as they were; and the rows decode on from there."""
    tok, pos, slab = started(form)
    lg, slab = step(form, slab, tok, pos)
    tok, pos = lg.argmax(-1).astype(np.int32), pos + 1
    by_kind = form.m.slab_bytes(slab)
    assert set(by_kind) == form.kinds
    assert sum(by_kind.values()) == sum(
        leaf.size * leaf.dtype.itemsize for leaf in leaves(slab))
    assert by_kind[form.grows] > 0
    fixed = form.fixed_kind is not None
    assert (by_kind.get(form.fixed_kind, 0) > 0) == fixed
    assert form.m.slab_dims(slab) == (3, 16)
    small = host(slab)
    grown = form.m.grow_slab(slab, 32)
    assert form.m.slab_dims(grown) == (3, 32)
    fresh = host(new_slab(form, 3, 32))     # what a new entry holds
    longer = 0
    for a, b, f in zip(small, host(grown), fresh):
        assert a.dtype == b.dtype
        if a.shape == b.shape:              # a ring or a state
            assert np.array_equal(a, b)
            continue
        longer += 1
        (axis,) = [i for i in range(a.ndim) if a.shape[i] != b.shape[i]]
        per = 16 // a.shape[axis]
        assert per in form.pers and b.shape[axis] == 32 // per
        head, tail = np.split(b, [16 // per], axis=axis)
        assert np.array_equal(head, a)
        assert np.array_equal(tail, np.split(f, [16 // per], axis=axis)[1])
    assert longer and (longer < len(small)) == fixed
    on_grown, _ = step(form, grown, tok, pos)
    tok, pos, slab = started(form)
    lg, slab = step(form, slab, tok, pos)
    on_small, _ = step(form, slab, lg.argmax(-1), pos + 1)
    np.testing.assert_allclose(on_grown, on_small, rtol=0, atol=1e-5)


# -- 6 ---------------------------------------------------------------------
def test_every_program_that_takes_the_slab_donates_it(form):
    """The caller keeps only the slab a program returns: the one it
    gave is deleted, whichever program took it, and the returned one
    is alive on the same geometry."""
    m, params = form.m, form.params
    tok, pos, slab = started(form)
    programs = [
        ("decode_step", lambda s: m.decode_step(
            params, s, put(tok), put(pos))[1]),
        ("decode_scan", lambda s: m.decode_scan(
            params, s, put(tok), put(pos + 1), 2)[1]),
        ("prefill_slab", lambda s: prefill(form, s, [ids_of(4, 6)],
                                           slots=[1])[1]),
    ]
    try:
        rows = m.export_slab_rows(slab, 0, 4)
        programs.append(("import_slab_rows",
                         lambda s: m.import_slab_rows(s, 2, rows)))
    except NotImplementedError:
        pass
    for name, program in programs:
        given = leaves(slab)
        slab = program(slab)
        assert all(leaf.is_deleted() for leaf in given), name
        assert not any(leaf.is_deleted() for leaf in leaves(slab)), name
        assert m.slab_dims(slab) == (3, 16), name


# -- 7 ---------------------------------------------------------------------
def test_a_changed_eps_is_a_new_program_and_a_repeat_is_none(form):
    """`eps` is a constant of the traced programs and rides their
    cache keys: calling again traces nothing, a changed `eps` traces
    each program anew (and moves the logits: never a stale program),
    and going back finds the first programs still there."""
    def retraces():
        return stats.cache_stats()["decode"]["retraces"]

    def three_programs():
        tok, pos, slab = started(form)                # prefill_slab
        lg, slab = step(form, slab, tok, pos)         # decode_step
        form.m.decode_scan(form.params, slab, put(tok), put(pos + 1), 3)
        return lg

    first = three_programs()
    warm = retraces()
    assert np.array_equal(three_programs(), first)
    assert retraces() == warm
    was = form.set_eps(1e-2)
    try:
        moved = three_programs()
        assert retraces() == warm + 3
        assert np.abs(moved - first).max() > 1e-3
        assert np.array_equal(three_programs(), moved)
    finally:
        form.set_eps(was)
    assert np.array_equal(three_programs(), first)
    assert retraces() == warm + 3


# -- 8 ---------------------------------------------------------------------
@pytest.mark.parametrize("form", FORMS[3:], indirect=True)
def test_a_row_decodes_as_if_it_were_alone(form):
    """Continuous batching: rows of one fused step sit at their own
    positions and share nothing. Each row's logits among two live
    neighbours are its logits in a slab whose other slot idles (never
    prefilled, token 0 at position 0 every step, as `serve.py` feeds
    a free slot). For the hybrid forms only: nothing else holds it
    there, where rows meet in the expert layer's routing; for
    `TransformerLM` the engine's own
    `test_serve_decode.py::test_join_leave_bit_identity_greedy` does.
    (Two slots, not one: the sorted expert path cannot trace for
    N*K < 4 assignment rows, ROADMAP C11.)"""
    tok, pos, slab = started(form)
    together = []
    for _ in range(3):
        lg, slab = step(form, slab, tok, pos)
        together.append(lg)
        tok, pos = lg.argmax(-1).astype(np.int32), pos + 1
    for r in range(3):
        lg, alone = prefill(form, new_slab(form, 2), [PROMPTS[r]],
                            slots=[1])
        for s in range(3):
            lg, alone = step(form, alone, [0, lg[-1].argmax()],
                             [0, LENS[r] + s])
            np.testing.assert_allclose(lg[1], together[s][r], rtol=0,
                                       atol=1e-5)
            assert lg[1].argmax() == together[s][r].argmax()


# -- 9 ---------------------------------------------------------------------
def test_the_token_program_is_the_step_and_the_hosts_argmax(form,
                                                            monkeypatch):
    """`decode_scan(k=1)` is what the engine dispatches for a single
    step while nobody samples: [1, B] int32, the token the host's
    `np.argmax` picks from `decode_step`'s logits (both first-max-wins
    on the same float bits), and the slab `decode_step` leaves, over 6
    steps (past the hybrid model's ring of 4). No loop is traced
    around one step. The hybrid forms' float leaves get the tolerance
    `test_a_block_is_its_steps` gives them."""
    import jax

    tok, pos, slab = started(form)
    want = []
    for _ in range(6):
        lg, slab = step(form, slab, tok, pos)
        tok, pos = lg.argmax(-1).astype(np.int32), pos + 1
        want.append(tok)
    by_logits = host(slab)
    tok, pos, slab = started(form)
    traced, program = [], form.m._slab_program

    def slab_program(kind, key_, fn, args, extras):
        traced.append(str(jax.make_jaxpr(fn)(*args)))
        return program(kind, key_, fn, args, extras)

    monkeypatch.setattr(form.m, "_slab_program", slab_program)
    for w in want:
        toks, slab = form.m.decode_scan(form.params, slab, put(tok),
                                        put(pos), 1)
        assert toks.shape == (1, 3) and toks.dtype == np.int32
        assert np.array_equal(np.asarray(toks)[0], w)
        tok, pos = w, pos + 1
    for a, b in zip(host(slab), by_logits):
        assert a.dtype == b.dtype
        if form.hybrid and a.dtype != np.int8:
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)
        else:
            assert np.array_equal(a, b)
    assert len(traced) == 6
    assert not any(" scan[" in j or " while[" in j for j in traced)


# -- 10 --------------------------------------------------------------------
class _Paths:
    """Which program each fused step of an engine took, beside the
    temperatures of the sessions that were live in it."""

    def __init__(self, eng, monkeypatch):
        self.steps = []
        m, fused = eng.model, eng._decode_fused_step
        step_, scan_ = m.decode_step, m.decode_scan

        def decode_step(*a):
            self.steps[-1][1] = "logits"
            return step_(*a)

        def decode_scan(params, slab, tok, pos, k):
            self.steps[-1][1] = f"tokens{k}"
            return scan_(params, slab, tok, pos, k)

        def fused_step(live, geom, dst):
            self.steps.append([[s.temperature for _, s in live], None])
            return fused(live, geom, dst)

        monkeypatch.setattr(m, "decode_step", decode_step)
        monkeypatch.setattr(m, "decode_scan", decode_scan)
        monkeypatch.setattr(eng, "_decode_fused_step", fused_step)


def _stream(eng, requests):
    """[(prompt, n, temperature, seed)] submitted in one go -> arrays."""
    replies = [eng.submit_decode(p, n, temperature=t, top_k=8 if t else 0,
                                 seed=seed) for p, n, t, seed in requests]
    return [np.asarray(r.result(timeout=300))[0] for r in replies]


@pytest.mark.parametrize("block", [1, 8])
@pytest.mark.parametrize(
    "form", ["lm-layernorm-tied", "hybrid-dense", "shortconv", "chunked"],
    indirect=True)
def test_greedy_streams_are_the_same_by_tokens_and_by_logits(
        form, block, monkeypatch):
    """Through `ServingEngine`, `decode_block` 1 and the default.
    All-greedy traffic never brings logits to the host: every fused
    step is `decode_scan` (k = 1 for a single step), and
    `decode_steps_tokens` counts every one of `decode_steps`. With a
    sampled session live beside them every step is `decode_step`, its
    logits on the host, the sampled row through `sample_fn` on its own
    key splits and the greedy rows through the host's argmax; the
    counter stands still for those steps. The greedy streams are the
    same streams both ways (and `generate()`'s, where the model has
    one); the sampled stream is the one it streams alone, which is
    today's path whole (and `generate()`'s with that seed)."""
    m = form.m
    greedy = [(p, 5, 0.0, 0) for p in PROMPTS]
    pilot = (ids_of(2, 9), 14, 0.8, 5)       # outlives the greedy ones
    eng = serve.ServingEngine(m, max_sessions=4, max_new_tokens=16,
                              prefill_batch=4, decode_block=block).start()
    try:
        eng.warm_decode(prompt_lens=(2, 7), max_new_tokens=16,
                        samplers=[(0.8, 8)])
        paths = _Paths(eng, monkeypatch)
        before = stats.decode_stats().snapshot()
        by_tokens = _stream(eng, greedy)
        mid = stats.decode_stats().snapshot()
        assert paths.steps and not any(
            any(temps) for temps, _ in paths.steps)
        assert {path for _, path in paths.steps} <= (
            {"tokens1"} if block == 1
            else {"tokens1", "tokens2", "tokens4", "tokens8"})
        assert (mid["decode_steps_tokens"] - before["decode_steps_tokens"]
                == mid["decode_steps"] - before["decode_steps"] > 0)
        alone = _stream(eng, [pilot])[0]
        del paths.steps[:]
        mid = stats.decode_stats().snapshot()
        beside, *by_logits = _stream(eng, [pilot] + greedy)
        after = stats.decode_stats().snapshot()
    finally:
        eng.stop()
    for temps, path in paths.steps:
        assert (path == "logits") == any(temps), (temps, path)
    mixed = [temps for temps, _ in paths.steps
             if any(temps) and not all(temps)]
    assert len(mixed) >= 4          # greedy rows beside the sampled one
    assert (after["decode_steps_tokens"] - mid["decode_steps_tokens"]
            == sum(int(path[6:]) for _, path in paths.steps
                   if path != "logits")
            < after["decode_steps"] - mid["decode_steps"])
    for a, b in zip(by_tokens, by_logits):
        assert np.array_equal(a, b)
    assert np.array_equal(beside, alone)
    if hasattr(m, "generate"):
        for (p, n, _, _), got in zip(greedy, by_tokens):
            assert np.array_equal(got, m.generate(p[None], n)[0])
        assert np.array_equal(alone, m.generate(
            pilot[0][None], pilot[1], temperature=0.8, top_k=8, seed=5)[0])
