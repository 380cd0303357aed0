"""`decode_attend` (ISSUE 30): one query a row against the positions
that row has written, reading of the slab only the row's own
128-position blocks. Interpreted on the CPU here; compiled by Mosaic at
the serving cells' widths in `tests/test_tpu_compile_widths.py`. The
plain reference is the two `einsum`s, the mask and the softmax that
`TransformerLM._slot_step` keeps for a rung of one block."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import device, stats, tensor
from singa_tpu.models.transformer import TransformerLM
from singa_tpu.ops import pallas_kernels as pk

TB = pk.DECODE_ATTEND_BLOCK
V = 97


def _reference(layer, q, pos, scale):
    """`_slot_step`'s lines over the whole rung, float32, "highest"."""
    T = layer.shape[-1]
    mask = pos[:, None] >= jnp.arange(T)[None, :]
    neg = jnp.asarray(jnp.finfo(jnp.float32).min / 2, jnp.float32)
    s = jnp.einsum("bhd,bhdk->bhk", q, layer[0],
                   precision="highest") * scale
    s = jnp.where(mask[:, None], s, neg)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhdk->bhd", p, layer[1], precision="highest")


@pytest.mark.parametrize("garbage", [False, True],
                         ids=["clean", "nan_beyond_the_rows_blocks"])
@pytest.mark.parametrize("T", [256, 512, 1024])
def test_rows_of_mixed_lengths_in_one_call(T, garbage):
    """Per-row `pos` mixed in one call, on both sides of a block's
    edge and at both ends of the rung. With `garbage` every block a
    row has not reached is NaN: the kernel must not have read it (the
    reference gets the same slab with zeros there: a NaN under its
    mask would still poison its weighted sum)."""
    rs = np.random.RandomState(T)
    pos = np.array([0, 127, 128, 129, T - 1, T // 2, 1, T - 129], np.int32)
    B, H, D = len(pos), 3, 16
    layer = rs.randn(2, B, H, D, T).astype(np.float32)
    q = rs.randn(B, H, D).astype(np.float32)
    seen = layer.copy()
    if garbage:
        for b, p in enumerate(pos):
            end = (p // TB + 1) * TB
            seen[:, b, :, :, end:] = np.nan
            layer[:, b, :, :, end:] = 0.0
    got = jax.jit(pk.decode_attend, static_argnums=3)(
        jnp.asarray(seen), jnp.asarray(q), jnp.asarray(pos), 0.25)
    want = _reference(jnp.asarray(layer), jnp.asarray(q), jnp.asarray(pos),
                      0.25)
    assert got.shape == (B, H, D) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6)


def test_a_rung_that_is_no_whole_number_of_blocks_is_refused():
    layer = jnp.zeros((2, 1, 1, 8, 192), jnp.float32)
    with pytest.raises(ValueError, match="192 positions"):
        pk.decode_attend(layer, jnp.zeros((1, 1, 8)), jnp.zeros(1, jnp.int32),
                         1.0)


@pytest.mark.parametrize("T,blocks", [(16, 0), (64, 0), (128, 0), (192, 0),
                                      (256, 2), (1024, 8)])
def test_which_rungs_take_the_kernel(T, blocks):
    """One block: nothing to skip, the `einsum`s stay (and with them
    every toy-width test's bit-identity with `generate()`)."""
    assert pk.decode_attend_blocks(T) == blocks


@pytest.fixture(scope="module")
def lm():
    dev = device.get_default_device()
    dev.SetRandSeed(11)
    m = TransformerLM(V, d_model=32, num_heads=2, num_layers=3, d_ff=64,
                      max_len=256)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32), device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


def _filled_slab(m, params, B, T, seed):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(*a.shape).astype(np.float32))
            for a in m.new_slab(params, B, T, None)]


POS = np.array([0, 126, 127, 128, 250], np.int32)


def test_a_block_of_four_steps_is_four_single_steps_token_for_token(lm):
    """`decode_scan` of k = 4 over a 256 rung against four
    `decode_step`s: rows cross a block's edge inside the block (126 ->
    129), the carry's `pos + 1` reaches the kernel, and the counters
    of a block are its steps' summed."""
    params = lm._decode_params()
    B, T, L = len(POS), 256, 3
    tok = jnp.asarray(np.arange(B, dtype=np.int32) + 3)
    toks, slab_k = lm.decode_scan(params, _filled_slab(lm, params, B, T, 1),
                                  tok, jnp.asarray(POS), 4)
    block = lm.take_step_counters()
    slab, t, p, got, read = _filled_slab(lm, params, B, T, 1), tok, POS, [], 0
    for _ in range(4):
        logits, slab = lm.decode_step(params, slab, t, jnp.asarray(p))
        one = lm.take_step_counters()
        assert one["attn_blocks_read"] == L * int(np.sum(p // TB + 1))
        assert one["attn_blocks_rung"] == L * B * 2
        read += one["attn_blocks_read"]
        t = jnp.argmax(logits, -1).astype(jnp.int32)
        p = p + 1
        got.append(np.asarray(t))
    assert np.array_equal(np.asarray(toks), np.stack(got))
    for a, b in zip(slab_k, slab):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert block == {"attn_blocks_read": read,
                     "attn_blocks_rung": 4 * L * B * 2}
    assert lm.take_step_counters() == {}


def test_the_step_on_the_kernel_is_the_step_on_the_einsums(lm, monkeypatch):
    """One `_slot_step` over the same slab both ways: the logits and
    the rows written agree to float32 rounding (the first layer's
    bit for bit: nothing before it differs); only the kernel's path
    counts blocks."""
    params = lm._decode_params()
    B, T = len(POS), 256
    tok = jnp.asarray(np.arange(B, dtype=np.int32) + 3)
    prec = tensor.get_matmul_precision()
    tensor.set_matmul_precision("highest")
    try:
        lg_k, slab_k, n_k = lm._slot_step(
            params, _filled_slab(lm, params, B, T, 2), tok, jnp.asarray(POS))
        monkeypatch.setattr(pk, "DECODE_ATTEND_MIN_BLOCKS", 3)
        lg_e, slab_e, n_e = lm._slot_step(
            params, _filled_slab(lm, params, B, T, 2), tok, jnp.asarray(POS))
    finally:
        tensor.set_matmul_precision(prec)
    np.testing.assert_allclose(np.asarray(lg_k), np.asarray(lg_e), rtol=0,
                               atol=2e-5)
    assert np.array_equal(np.asarray(slab_k[0]), np.asarray(slab_e[0]))
    for a, b in zip(slab_k, slab_e):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=2e-5)
    assert list(np.asarray(n_k)) == [3 * 7, 3 * B * 2]
    assert list(np.asarray(n_e)) == [0, 0]


@pytest.mark.parametrize("case", ["one_block_rung", "int8_slab"])
def test_what_keeps_the_einsums_counts_no_blocks(lm, case):
    """A rung of one block, and the int8 slab on any rung (its layer is
    dequantized whole: ROADMAP A3's second half)."""
    quant = case == "int8_slab"
    params = lm._decode_params_quant() if quant else lm._decode_params()
    B, T = 3, 256 if quant else 128
    slab = lm.new_slab(params, B, T, None)
    assert isinstance(slab[0], tuple) == quant
    vec = jnp.asarray(np.array([0, 5, 100], np.int32))
    logits, _ = lm.decode_step(params, slab, vec, vec)
    assert np.isfinite(np.asarray(logits)).all()
    assert lm.take_step_counters() == {"attn_blocks_read": 0,
                                       "attn_blocks_rung": 0}


def test_a_served_stream_on_a_two_block_rung_counts_its_blocks(lm):
    """Through `ServingEngine`: a session whose prompt and budget need
    the 256 rung is served by the kernel's path, the engine adds the
    step counters into `cache_stats()["decode"]`, and the stream is the
    greedy stream of the model's own single steps on the einsums'
    path (no near-tie at this seed)."""
    from singa_tpu import serve

    prompt = (np.arange(120, dtype=np.int32) * 7 + 1) % V
    before = dict(stats.cache_stats()["decode"])
    eng = serve.ServingEngine(lm, max_sessions=2, max_new_tokens=24,
                              decode_block=4).start()
    try:
        got = np.asarray(eng.submit_decode(prompt, 24).result(timeout=600))
        slots, rung_t = eng._slab_dims()
        assert rung_t == 256
    finally:
        eng.stop()
    after = stats.cache_stats()["decode"]
    steps = after["decode_steps"] - before["decode_steps"]
    read = after["attn_blocks_read"] - before["attn_blocks_read"]
    rung = after["attn_blocks_rung"] - before["attn_blocks_rung"]
    assert steps >= 23 and rung == steps * 3 * slots * 2
    # the session's row crosses into its second block at position 128;
    # the other rows are empty and read one block each
    assert steps * 3 * slots < read < rung
    assert np.array_equal(got[0, :120], prompt)
    assert np.array_equal(got, lm.generate(prompt[None], 24))


def test_a_program_lowers_the_kernel_once_for_all_its_layers(lm):
    """`decode_attend` is a jitted function, so a program that calls
    it a layer holds ONE lowered copy and L calls of it. Lowered a
    layer (12 Mosaic modules a program built in Python, program after
    program of `warm_decode`) it cost `gpt2-serve-decode` 20 s of
    `setup_s` against a warm compile cache (PERF.md, PR 30)."""
    params = lm._decode_params()
    B, T = 4, 256
    vec = jnp.zeros(B, jnp.int32)
    text = jax.jit(lm._slot_step).lower(
        params, lm.new_slab(params, B, T, None), vec, vec).as_text()
    assert text.count("func.func private @decode_attend") == 1
    assert text.count("call @decode_attend") == len(params["blocks"])


def test_a_position_past_the_rung_reads_no_block_past_it():
    """`serve.py` grows the slab before a row reaches its rung's end,
    so `pos >= T` reaches no program; the kernel still clamps its DMAs
    to the rung (a read past the layer would be another row's, or past
    the buffer) and then sees the whole row, as the reference does."""
    rs = np.random.RandomState(3)
    layer = jnp.asarray(rs.randn(2, 2, 2, 16, 256).astype(np.float32))
    q = jnp.asarray(rs.randn(2, 2, 16).astype(np.float32))
    pos = jnp.asarray(np.array([256, 1000], np.int32))
    got = pk.decode_attend(layer, q, pos, 0.25)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_reference(layer, q, pos, 0.25)),
                               rtol=0, atol=2e-6)
