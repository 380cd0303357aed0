"""The learned block-sparse mixture-of-experts LM against its plain
reference (ISSUE 39), at toy widths with the served configuration's
mechanism kept: d 64, 8 query heads over 2 key/value groups of 16, an
indexer of 2 heads of 16 a group, blocks of N = 8 positions, the top 4
of the complete blocks beside block 0 and the 2 local ones (at most 7
blocks, 56 positions, a query reads), a dense first layer and two
routed ones of 4 held experts of 16 with a shared expert, vocabulary
64. Contexts of about 200 positions (25 blocks), so the selection
drops most of them. Seeded float32 weights on the CPU at "highest":
2e-4 on logits whose scale is about 0.5 is float32 rounding through 3
layers (what is read is under 1e-5), and no room for any of the
planted faults of the selection, each of which moves them by 1e-2 and
more.
"""
import functools
import hashlib

import numpy as np
import pytest

from perfbench.reference import minimax_m3_control
from perfbench.reference import minimax_m3_ref as ref
from singa_tpu import device, serve, stats, tensor
from singa_tpu.models.block_sparse_moe import (BlockSparseMoELM,
                                               attend_selected)

V, N, TOP, LOCAL = 64, 8, 4, 2
KW = dict(d_model=64, num_heads=8, kv_heads=2, head_dim=16, rotary_dim=8,
          rope_theta=5e6, index_heads=2, index_dim=16, block=N,
          top_blocks=TOP, local_blocks=LOCAL, moe_layers=(0, 1, 1), d_ff=96,
          d_ff_expert=32, d_ff_shared=32, n_experts=16, experts_per_token=4,
          held=(4, 4), routed_scale=2.0, norm_eps=1e-6, max_len=256,
          prefill_block=32, prefill_tile=16, init_std=0.1)
ARCH = {**{k: KW[k] for k in (
    "num_heads", "kv_heads", "head_dim", "rotary_dim", "rope_theta",
    "index_heads", "index_dim", "block", "top_blocks", "local_blocks",
    "moe_layers", "experts_per_token", "held", "routed_scale",
    "norm_eps")}, "swiglu_alpha": 1.702, "swiglu_limit": 7.0}
TOL = dict(rtol=0, atol=2e-4)
READ = (1 + LOCAL + TOP) * N      # positions a query reads at most


@pytest.fixture(autouse=True)
def _highest():
    before = tensor.get_matmul_precision()
    tensor.set_matmul_precision("highest")
    yield
    tensor.set_matmul_precision(before)


def build(seed=3, **over):
    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    m = BlockSparseMoELM(V, **{**KW, **over})
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32), device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return build()


def states_of(m):
    return {k: v.data for k, v in m.get_states().items()}


def ref_logits(m, ids, **over):
    return np.asarray(ref.logits(states_of(m), np.asarray(ids),
                                 **{**ARCH, **over}))


def ids_of(shape, seed=0):
    return np.random.default_rng(seed).integers(0, V, shape, dtype=np.int32)


def put(a):
    import jax.numpy as jnp

    return jnp.asarray(a)


def fresh_slab(m, slots=2, seq=256):
    import jax

    return m.new_slab(m._decode_params(), slots, seq, jax.devices()[0])


def prefill(m, slab, rows, bucket, slots=None):
    ids = np.zeros((len(rows), bucket), np.int32)
    for r, row in enumerate(rows):
        ids[r, :len(row)] = row
    n = np.asarray([len(r) for r in rows], np.int32)
    slots = np.arange(len(rows), dtype=np.int32) if slots is None else slots
    lg, slab = m.prefill_slab(m._decode_params(), slab, put(ids), put(n),
                              put(np.asarray(slots, np.int32)))
    return np.asarray(lg), slab


def step(m, slab, tok, pos):
    out, slab = m.decode_step(m._decode_params(), slab,
                              put(np.asarray(tok, np.int32)),
                              put(np.asarray(pos, np.int32)))
    return np.asarray(out), slab


def bucket_of(n):
    return 1 << (n - 1).bit_length()


# -- (a) the eval forward and the selection -----------------------------------
@pytest.mark.parametrize("S", [5, READ, READ + 1, 77, 200])
def test_a_eval_forward_equals_reference(model, S):
    ids = ids_of((2, S), seed=S)
    got = np.asarray(model.forward(tensor.from_numpy(ids)).data)
    np.testing.assert_allclose(got, ref_logits(model, ids), **TOL)


def test_a_the_programs_selection_is_the_references(model):
    """Block ids by (position, layer, group), ascending: the prefill's
    `lax.top_k` and the reference's ranking by counting pick the same
    blocks, and past 7 blocks they drop some."""
    ids = ids_of((2, 200), seed=7)
    mine = [np.asarray(p) for p in model.picks(tensor.from_numpy(ids))]
    theirs = [np.asarray(p) for p in ref.picks(states_of(model), ids, **ARCH)]
    assert ref.selection_disagreement(mine, theirs) == 0.0
    for p, r in zip(mine, theirs):
        assert p.shape == r.shape == (2, 200, 2, 1 + LOCAL + TOP)
        n = (r >= 0).sum(-1)
        assert np.array_equal(np.where(r >= 0, r, 0), p)
        # every block up to the query's own while there are no more
        # than TOP candidates; then 1 + LOCAL + TOP of them
        c = np.arange(200) // N
        assert np.array_equal(n[0, :, 0], np.minimum(c + 1, 1 + LOCAL + TOP))
        assert (p[..., 0] == 0).all()


def test_a_below_the_top_the_attention_is_plain_causal(model):
    """Where a query has no more candidates than the top takes (t <
    (1 + LOCAL + TOP) N at this size, 19 x 128 at the published one),
    the selection reads every block and the logits are those of a model
    whose top takes everything; one block later they are not."""
    every = build(top_blocks=10 ** 6)
    ids = ids_of((1, READ + N), seed=9)
    a = np.asarray(model.forward(tensor.from_numpy(ids)).data)[0]
    b = np.asarray(every.forward(tensor.from_numpy(ids)).data)[0]
    np.testing.assert_allclose(a[:READ], b[:READ], rtol=0, atol=1e-6)
    assert np.abs(a[READ:] - b[READ:]).max() > 1e-3


def _variant(what, ids, monkeypatch):
    """Logits of the reference with one of its rules broken (its
    programs traced again: jax keeps them by function)."""
    import jax
    import jax.numpy as jnp

    m = build()
    if what == "no_plus_one":
        monkeypatch.setattr(ref, "_rms", lambda x, g, eps: x / jnp.sqrt(
            eps + (x * x).mean(-1, keepdims=True)) * g)
    elif what == "mean_pool":
        monkeypatch.setattr(ref, "pooled_keys", lambda ki, n: ki.reshape(
            -1, n, *ki.shape[1:]).mean(1))
    elif what == "no_scale":
        return ref_logits(m, ids, routed_scale=1.0), m
    elif what == "plain_act":
        monkeypatch.setattr(ref, "_act", lambda g, u, a, lim: jax.nn.silu(g)
                            * u)
    jax.clear_caches()
    return ref_logits(m, ids), m


@pytest.mark.parametrize("what", ["no_plus_one", "mean_pool", "no_scale",
                                  "plain_act"])
def test_a_the_reference_would_notice(what, monkeypatch):
    """Each rule the toy's draws let one see: a gain without its `+ 1`,
    blocks pooled by their mean, the routed part unscaled, silu in
    place of swigluoai each move the logits past TOL."""
    ids = ids_of((1, 160), seed=13)
    import jax

    broken, m = _variant(what, ids, monkeypatch)
    monkeypatch.undo()
    jax.clear_caches()
    assert np.abs(broken - ref_logits(m, ids)).max() > 1e-2


# -- (b) prefill, then decode through the slab --------------------------------
@pytest.mark.parametrize("P,total", [
    (5, 60),        # all under the top: plain causal, then past it
    (30, 80),       # the prefill's last block open; six boundaries
    (100, 140),     # a bucket of 128 padded; five boundaries
    (150, 210)],    # a pooled key half from the prefill, half from steps
    ids=["short", "open_block", "padded_bucket", "block_split"])
def test_b_prefill_then_steps_equal_reference(model, P, total):
    m = model
    full = ids_of((total,), seed=P)
    want = ref_logits(m, full[None])[0]
    lg, slab = prefill(m, fresh_slab(m), [full[:P]], bucket_of(P))
    np.testing.assert_allclose(lg[0], want[P - 1], **TOL)
    assert (total - 1) // N - P // N >= 3       # boundaries crossed
    for t in range(P, total):
        out, slab = step(m, slab, [full[t], 0], [t, 0])
        np.testing.assert_allclose(out[0], want[t], **TOL)


def test_b_the_steps_pick_what_the_reference_picks(model, monkeypatch):
    """The decode step's own block ids (eager, recorded where
    `selected_ids` hands them to the attention) equal the reference's
    at every position crossed, layer and group, a pooled key half
    written by the prefill and half by the steps among them."""
    import jax

    m = model
    full = ids_of((160,), seed=21)
    theirs = [np.asarray(p)[0] for p in ref.picks(states_of(m), full[None],
                                                  **ARCH)]
    _, slab = prefill(m, fresh_slab(m), [full[:140]], 256)
    seen = []
    inner = BlockSparseMoELM.selected_ids

    def record(self, mask):
        ids, n = inner(self, mask)
        seen.append((np.asarray(ids), np.asarray(n)))
        return ids, n

    monkeypatch.setattr(BlockSparseMoELM, "selected_ids", record)
    params = m._decode_params()
    with jax.disable_jit():
        for t in range(140, 160):
            seen.clear()
            _, slab, _ = m._slot_step(params, slab, put([full[t], 0]),
                                      put([t, 0]))
            for layer, (ids, n) in enumerate(seen):
                want = theirs[layer][t]                    # [G, width]
                for g in range(2):
                    assert n[0, g] == (want[g] >= 0).sum()
                    assert np.array_equal(ids[0, g, :n[0, g]],
                                          want[g, :n[0, g]])


def test_b_a_pad_row_writes_nothing_and_pooled_keys_cover_real_positions(
        model):
    """A prefill writes keys and values over the bucket and the pooled
    keys of the blocks that hold real positions, over those alone: a
    block the prompt ends inside holds the max of its real positions,
    and every block past it the lowest value, which a step replaces
    when it enters the block. A pad row's slot (out of bounds) keeps
    what it held."""
    import jax.numpy as jnp

    m = model
    prompt = ids_of((21,), seed=5)
    _, slab = prefill(m, fresh_slab(m), [prompt, prompt[:3]], 32,
                      slots=[0, 2])
    low = jnp.finfo(jnp.float32).min
    for c in slab:
        kp = np.asarray(c["kp"])
        assert (kp[1] == low).all() and (kp[0, :, :, 3:] == low).all()
        assert (kp[0, :, :, :3] > low).all()
        assert not np.asarray(c["k"])[1].any()
    # the max of the real positions of the third block: 16 .. 20
    h = np.asarray(m.forward(tensor.from_numpy(prompt[None])).data)
    assert h.shape == (1, 21, V)


# -- (c) through the engine ------------------------------------------------------
def _serve(m, requests, **kw):
    eng = serve.ServingEngine(m, max_sessions=2, max_new_tokens=64,
                              prefill_batch=1, decode_block=4, **kw).start()
    try:
        eng.warm_decode(prompt_lens=(3, 128), max_new_tokens=64)
        out = []
        for p, n in requests:
            out.append(np.asarray(eng.submit_decode(p, n).result(
                timeout=300))[0])
        return out
    finally:
        eng.stop()


def test_c_through_the_engine_sessions_stream_what_the_reference_picks(
        model):
    """Through `ServingEngine` (`submit_decode`, the dispatcher, the
    token program and run-ahead blocks): each reply is what the
    reference picks; the gauges name the two kinds of the slab and the
    three counters add up as stated."""
    m = model
    requests = [(ids_of((120,), 11), 60), (ids_of((3,), 12), 50)]
    stats.reset_cache_stats()
    got = _serve(m, requests)
    d = stats.cache_stats()["decode"]
    assert d["host_leaves_per_call"] == 0
    assert d["cache_bytes_ring"] == d["cache_bytes_state"] == 0
    rung = d["cache_bytes_context"] // (3 * 2 * 2 * 2 * 16 * 4)
    assert rung >= 128 + 64 and rung & (rung - 1) == 0
    assert d["cache_bytes_blockkey"] == 3 * 2 * 2 * 16 * (rung // N) * 4
    steps = d["decode_steps"]
    assert d["msa_blocks_selected"] > 0
    assert d["msa_positions_read"] == N * d["msa_blocks_selected"]
    assert d["msa_positions_read"] <= 3 * 2 * 2 * READ * steps
    assert 0 < d["msa_positions_read"] < d["msa_positions_held"]
    for (prompt, n), full in zip(requests, got):
        assert len(full) == len(prompt) + n
        want = ref_logits(m, full[None])[0]
        at = np.arange(len(prompt) - 1, len(full) - 1)
        assert (want[at].max(-1) - want[at, full[at + 1]]).max() < 2e-4


def test_c_the_step_counts_what_it_read_held_and_picked(model):
    """One step at positions 180 and 3: a row reads 1 + LOCAL + TOP
    blocks in each layer and group once it has more candidates, every
    block up to its own before; it holds pos + 1 positions."""
    m = model
    _, slab = prefill(m, fresh_slab(m), [ids_of((180,), 2), ids_of((3,), 3)],
                      256)
    params = m._decode_params()
    _, _, counters = m._slot_step(params, slab, put([1, 2]), put([180, 3]))
    got = dict(zip(m.step_counter_names, np.asarray(counters)))
    picked = 3 * 2 * ((1 + LOCAL + TOP) + 1)
    assert got["msa_blocks_selected"] == picked
    assert got["msa_positions_read"] == N * picked
    assert got["msa_positions_held"] == 3 * 2 * (181 + 4)


# -- (d) growth --------------------------------------------------------------------
def test_d_growth_pads_every_kind_and_streams_go_on(model):
    m = model
    full = ids_of((150,), seed=4)
    want = ref_logits(m, full[None])[0]
    _, slab = prefill(m, fresh_slab(m, seq=128), [full[:100]], 128)
    for t in range(100, 120):
        _, slab = step(m, slab, [full[t], 0], [t, 0])
    grown = m.grow_slab(slab, 256)
    assert m.slab_dims(slab) == (2, 128) and m.slab_dims(grown) == (2, 256)
    for old, new in zip(slab, grown):
        kp = np.asarray(new["kp"])
        assert kp.shape == (2, 2, 16, 256 // N)
        assert np.array_equal(kp[..., :128 // N], np.asarray(old["kp"]))
        assert (kp[..., 128 // N:] == np.finfo(np.float32).min).all()
    assert set(m.slab_bytes(grown)) == {"context", "blockkey"}
    slab = grown
    for t in range(120, len(full)):
        out, slab = step(m, slab, [full[t], 0], [t, 0])
        np.testing.assert_allclose(out[0], want[t], **TOL)


# -- (e) the planted faults fail the comparison -----------------------------------
@pytest.mark.parametrize("fault", minimax_m3_control.FAULTS)
def test_e_a_planted_fault_fails_the_comparison(fault, monkeypatch):
    """The control's three faults, planted as it plants them: a stream
    prefilled to 100 positions and decoded to 180 leaves the reference
    by far more than TOL (without the fault: the tests above)."""
    for name in ("_selection", "_slot_step", "__init__"):
        monkeypatch.setattr(BlockSparseMoELM, name,
                            BlockSparseMoELM.__dict__[name])
    minimax_m3_control.plant(fault)
    m = build()
    full = ids_of((180,), seed=31)
    want = ref_logits(m, full[None])[0]
    lg, slab = prefill(m, fresh_slab(m), [full[:100]], 128)
    worst = np.abs(lg[0] - want[99]).max()
    for t in range(100, len(full)):
        out, slab = step(m, slab, [full[t], 0], [t, 0])
        worst = max(worst, np.abs(out[0] - want[t]).max())
    assert worst > 1e-2


# -- (f) the kernel against the gather -----------------------------------------------
@pytest.mark.parametrize("B,G,Hg,D,T,blk", [
    (3, 2, 4, 16, 64, 8), (2, 4, 16, 128, 512, 128), (2, 1, 8, 16, 16, 8)])
def test_f_selected_blocks_attend_is_the_gather(B, G, Hg, D, T, blk):
    """`selected_blocks_attend` interpreted against `attend_selected`
    (XLA's gather of the same blocks): ids ascending, counts from one
    block to all, a row whose own block is cut at pos; bfloat16 keys and
    values as the slab holds them."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.ops.pallas_kernels import selected_blocks_attend

    rng = np.random.default_rng(B * T)
    nb, S = T // blk, min(T // blk, 5)
    pos = rng.integers(0, T, B).astype(np.int32)
    pos[0] = T - 1
    ids = np.zeros((B, G, S), np.int32)
    n = np.zeros((B, G), np.int32)
    for b in range(B):
        c = pos[b] // blk
        for g in range(G):
            pick = np.union1d([0, c], rng.choice(c + 1, min(c + 1, S - 2),
                                                 replace=False))[:S]
            ids[b, g, :len(pick)] = np.sort(pick)
            n[b, g] = len(pick)
    keys = jax.random.split(jax.random.PRNGKey(T), 3)
    q = jax.random.normal(keys[0], (B, G, Hg, D), jnp.bfloat16)
    k = jax.random.normal(keys[1], (B, G, D, T), jnp.bfloat16)
    v = jax.random.normal(keys[2], (B, G, T, D), jnp.bfloat16)
    got = selected_blocks_attend(q, k, v, put(ids), put(n), put(pos), blk)
    want = attend_selected(q, k, v, put(ids), put(n), put(pos), blk)
    assert got.dtype == jnp.float32 and got.shape == (B, G, Hg, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-2)
    assert nb >= S


# -- (g) the routed layer ---------------------------------------------------------
def test_g_swigluoai_clamps_beyond_seven():
    import jax.numpy as jnp

    from singa_tpu.models.routed_experts import swigluoai

    g = jnp.asarray([-20.0, -7.5, -1.0, 0.0, 3.0, 7.0, 7.5, 50.0])
    u = jnp.asarray([-30.0, 9.0, -8.0, 2.0, 0.5, -7.0, 100.0, -0.5])
    got = np.asarray(swigluoai(g, u))
    gc = np.minimum(np.asarray(g), 7.0)
    want = gc / (1 + np.exp(-1.702 * gc)) * (np.clip(np.asarray(u), -7, 7)
                                              + 1)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # past the limit nothing moves: g at 7.5 and 50 is g at 7
    assert got[6] == pytest.approx(7.0 / (1 + np.exp(-1.702 * 7)) * 8.0)
    assert got[7] == pytest.approx(7.0 / (1 + np.exp(-1.702 * 7)) * 0.5)
    assert got.dtype == np.float32


def test_g_sixteen_shares_and_the_shared_expert_make_the_whole_layer():
    """The deployment the cut stands for: 16 chips, 8 of 128 experts
    each. The routed parts of the 16 shares, plus the shared expert
    once, are the reference's layer with every expert held."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.models.routed_experts import routed_experts, swigluoai

    d, f, E, K = 32, 16, 128, 4
    keys = jax.random.split(jax.random.PRNGKey(1), 8)
    ffn = {"W_r": jax.random.normal(keys[0], (d, E)) * 0.3,
           "b": jax.random.normal(keys[1], (E,)) * 0.1,
           "W_g": jax.random.normal(keys[2], (E, d, f)) * 0.3,
           "W_u": jax.random.normal(keys[3], (E, d, f)) * 0.3,
           "W_d": jax.random.normal(keys[4], (E, f, d)) * 0.3}
    shared = [jax.random.normal(k, s) * 0.3 for k, s in zip(
        keys[5:], ((d, f), (d, f), (f, d)))]
    h = jax.random.normal(jax.random.PRNGKey(2), (40, d))
    g = jnp.zeros(d)
    n = ref._rms(h, g, 1e-6)
    parts = sum(routed_experts(
        {**ffn, **{w: ffn[w][8 * i:8 * i + 8] for w in ("W_g", "W_u", "W_d")}},
        n, "highest", held=(8 * i, 8), experts_per_token=K,
        act=swigluoai, scale=2.0)[0] for i in range(16))
    with jax.default_matmul_precision("highest"):
        mine = parts + swigluoai(n @ shared[0], n @ shared[1]) @ shared[2]
    arch = ref._arch({"norm_eps": 1e-6, "experts_per_token": K,
                      "held": (0, E), "routed_scale": 2.0,
                      "swiglu_alpha": 1.702, "swiglu_limit": 7.0})
    ln = jnp.zeros(d)
    wts = ref.router_weights(h, (ln, ffn["W_r"], ffn["b"]), arch)
    y = ref.expert_layer(jnp.zeros_like(h), h, jnp.ones(40),
                         (ln, *shared), arch)
    for e in range(E):
        y = ref.expert_layer(y, h, wts[:, e], (ln, ffn["W_g"][e],
                                               ffn["W_u"][e], ffn["W_d"][e]),
                             arch)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(y), rtol=0,
                               atol=2e-5)


def _old_routed_experts(ffn, x, prec, *, held, experts_per_token,
                        dense_rows=256, sum_eps=0.0):
    """`routed_experts` as PR 38 left it, word for word."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    first, E = held
    K = experts_per_token
    N = x.shape[0]
    all_held = first == 0 and E == ffn["W_r"].shape[-1]
    with jax.named_scope("moe_router"):
        sig = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), ffn["W_r"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, idx = lax.top_k(sig + ffn["b"], K)
        chosen = jnp.take_along_axis(sig, idx, -1)
        total = jnp.sum(chosen, -1, keepdims=True)
        if sum_eps:
            total = total + sum_eps
        w = chosen / total
        local = idx - first
        here = (local >= 0) & (local < E)
        local = jnp.where(here, local, E)
        counts = jnp.zeros(E + 1, jnp.int32).at[
            local.reshape(-1)].add(1)[:E]
    with jax.named_scope("moe_experts"):
        if N <= dense_rows:
            cw = jnp.sum(jax.nn.one_hot(local, E + 1, dtype=jnp.float32)
                         [..., :E] * w[..., None], 1)
            g = jnp.einsum("nd,edf->enf", x, ffn["W_g"], precision=prec)
            u = jnp.einsum("nd,edf->enf", x, ffn["W_u"], precision=prec)
            a = jax.nn.silu(g) * u * cw.T[:, :, None].astype(x.dtype)
            y = jnp.einsum("enf,efd->nd", a, ffn["W_d"], precision=prec)
            return y, counts
        flat = local.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        ws = jnp.where(here, w, 0.0).reshape(-1)
        at = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * K, dtype=order.dtype)).reshape(N, K)
        rd = functools.partial(lax.ragged_dot, group_sizes=counts,
                               precision=prec)

        def through(rows):
            o = order[:rows]
            xs = x[o // K]
            a = jax.nn.silu(rd(xs, ffn["W_g"])) * rd(xs, ffn["W_u"])
            y = rd(a, ffn["W_d"])
            y = jnp.where((flat[o] < E)[:, None],
                          y * ws[o][:, None].astype(y.dtype), 0)
            y = jnp.where((at < rows)[..., None],
                          y[jnp.minimum(at, rows - 1)], 0)
            return y.astype(jnp.float32).sum(1).astype(x.dtype)

        if all_held:
            return through(N * K), counts
        few = N * K // 4
        y = lax.cond(counts.sum() <= few, lambda: through(few),
                     lambda: through(N * K))
        return y, counts


@pytest.mark.parametrize("shapes", [
    # mimo-v2.5: 16 of 256 experts, d 4096, f 2048, top 8; a step's 128
    # rows and a prefill's 2,048
    (4096, 2048, 256, 8, (0, 16), 0.0, 128),
    (4096, 2048, 256, 8, (0, 16), 0.0, 2048),
    # lfm2-24b-a2b: all 64 experts, d 2048, f 1536, top 4, eps 1e-6
    (2048, 1536, 64, 4, (0, 64), 1e-6, 128),
    (2048, 1536, 64, 4, (0, 64), 1e-6, 1024)],
    ids=["mimo_step", "mimo_prefill", "lfm2_step", "lfm2_prefill"])
def test_g_the_default_routed_layer_is_the_same_program(shapes):
    """With no activation and no scale named, `routed_experts` lowers
    at both served models' shapes to the program PR 38's did, text for
    text: the two cells that call it run what they ran."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.models.routed_experts import routed_experts

    d, f, E, K, held, eps, rows = shapes
    H = held[1]
    sds = jax.ShapeDtypeStruct
    ffn = {"W_r": sds((d, E), jnp.float32), "b": sds((E,), jnp.float32),
           "W_g": sds((H, d, f), jnp.bfloat16),
           "W_u": sds((H, d, f), jnp.bfloat16),
           "W_d": sds((H, f, d), jnp.bfloat16)}
    x = sds((rows, d), jnp.bfloat16)
    texts = []
    for fn in (routed_experts, _old_routed_experts):
        def layer(ffn, x, fn=fn):
            return fn(ffn, x, "default", held=held, experts_per_token=K,
                      sum_eps=eps)
        texts.append(jax.jit(layer).lower(ffn, x).as_text())
    assert hashlib.sha1(texts[0].encode()).hexdigest() == hashlib.sha1(
        texts[1].encode()).hexdigest()


# -- (h) what is not implemented says so by mechanism ------------------------------
def test_h_what_is_not_implemented_says_so(model):
    with pytest.raises(NotImplementedError, match="pooled block keys"):
        model.export_slab_rows(fresh_slab(model), 0, 4)
    with pytest.raises(NotImplementedError, match="indexer"):
        model.train_one_batch(None, None)
    with pytest.raises(NotImplementedError, match="key/value groups"):
        model._shard_decode_params(None, None)
    with pytest.raises(ValueError, match="whole number of blocks"):
        model.new_slab(model._decode_params(), 2, 12, None)
