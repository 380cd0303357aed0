"""The decode tier's argument tree holds arrays only (ISSUE 25).

jax transfers a Python scalar leaf of a jitted call's tree to the
device on EVERY call: on the v5e the 25 LayerNorm `eps` floats cost 5
of each enqueue's 6.6 ms. These tests pin the rule and what it must
not break:

  - every leaf of `_decode_params()`, `_decode_params_quant()` and
    `_shard_decode_params()` is an array, for LayerNorm and RMSNorm
    alike, and a decode step, a run-ahead block and a cohort prefill
    with explicitly placed inputs move nothing else to the device;
  - `eps` is still the layer's own value — a constant of the traced
    program, keyed in the program cache and in the AOT store, so two
    models that differ only in `eps` never share a program;
  - a warmed engine reports `host_leaves_per_call == 0`.
"""
import numpy as np
import pytest

from singa_tpu import device, export_cache, serve, stats, tensor
from singa_tpu.models.transformer import TransformerLM

V, D, H, L = 64, 32, 2, 2
MAXLEN = 16
B, T = 2, 8  # slab: slots x sequence rung


def _build(norm="layer", eps=None, seed=3):
    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    m = TransformerLM(V, d_model=D, num_heads=H, num_layers=L,
                      max_len=MAXLEN, norm=norm)
    if eps is not None:
        _set_eps(m, eps)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                 device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


def _set_eps(m, eps):
    for blk in m.blocks._seq:
        blk.ln1.eps = blk.ln2.eps = eps
    m.ln_f.eps = eps


def _tree(m, form):
    """(params, slab) in one of the three forms the decode tier runs."""
    import jax
    from jax.sharding import Mesh

    params = (m._decode_params_quant() if form == "quant"
              else m._decode_params())
    slab = m.new_slab(params, B, T, None)
    if form == "shard":
        mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
        params = m._shard_decode_params(params, mesh)
    return params, slab


def _next_logits(m, ids):
    """Next-token logits after `ids` [P] through the decode tier:
    a cohort prefill of all but the last token, then one fused step."""
    import jax.numpy as jnp

    params, slab = _tree(m, "plain")
    P = len(ids)
    bucket = np.zeros((1, T), np.int32)
    bucket[0, :P - 1] = ids[:-1]
    _, slab = m.prefill_slab(params, slab, jnp.asarray(bucket),
                             jnp.asarray([P - 1], jnp.int32),
                             jnp.asarray([0], jnp.int32))
    tok = jnp.asarray([ids[-1], 0], jnp.int32)
    pos = jnp.asarray([P - 1, 0], jnp.int32)
    logits, _ = m.decode_step(params, slab, tok, pos)
    return np.asarray(logits)[0]


@pytest.mark.parametrize("form", ["plain", "quant", "shard"])
@pytest.mark.parametrize("norm", ["layer", "rms"])
def test_tree_holds_arrays_only_and_calls_transfer_nothing(norm, form):
    import jax

    m = _build(norm)
    params, slab = _tree(m, form)
    leaves = jax.tree_util.tree_leaves(params)
    bad = [type(l).__name__ for l in leaves
           if not isinstance(l, (jax.Array, np.ndarray))]
    assert not bad, f"non-array leaves in the decode tree: {bad}"
    # the norm specs keep their structural dispatch: tuple length
    n_norm = 1 if norm == "rms" else 2
    assert len(params["ln_f"]) == n_norm
    assert all(len(blk[k]) == n_norm
               for blk in params["blocks"] for k in ("ln1", "ln2"))

    tok = jax.device_put(np.array([1, 2], np.int32))
    pos = jax.device_put(np.array([3, 4], np.int32))
    ids = jax.device_put(np.ones((1, 4), np.int32))
    n_real = jax.device_put(np.array([3], np.int32))
    slots = jax.device_put(np.array([1], np.int32))

    def calls(slab):      # each program donates the slab it is given
        _, slab = m.decode_step(params, slab, tok, pos)
        _, slab = m.decode_scan(params, slab, tok, pos, 2)
        return m.prefill_slab(params, slab, ids, n_real, slots)[1]

    slab = calls(slab)  # compile outside the guard
    with jax.transfer_guard_host_to_device("disallow"):
        calls(slab)


def test_eps_is_the_layers_own_and_keys_the_program():
    """Same weights, `eps` 1e-5 against 1e-2: different logits, each
    model's equal to its own eval forward; different fingerprints, so
    different AOT keys."""
    import jax.numpy as jnp

    ids = np.array([5, 9, 2, 7, 11], np.int32)
    got = {}
    for eps in (1e-5, 1e-2):
        m = _build(eps=eps)
        got[eps] = (m, _next_logits(m, ids))
        ref = m.forward(tensor.from_numpy(ids[None])).to_numpy()[0, -1]
        np.testing.assert_allclose(got[eps][1], ref,
                                   rtol=1e-4, atol=1e-5)
        full = m.generate(ids[None], 1)
        assert full[0, -1] == got[eps][1].argmax()
    (m1, l1), (m2, l2) = got[1e-5], got[1e-2]
    for (n1, p1), (n2, p2) in zip(sorted(m1.get_params().items()),
                                  sorted(m2.get_params().items())):
        assert np.array_equal(p1.to_numpy(), p2.to_numpy()), (n1, n2)
    assert np.abs(l1 - l2).max() > 1e-3  # a baked default would tie
    assert m1.topology_fingerprint() != m2.topology_fingerprint()
    keys = []
    for m in (m1, m2):
        params, slab = _tree(m, "plain")
        args = (params, slab, jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32))
        keys.append(export_cache.step_key(m, None, "decode_step",
                                          args)[0])
    assert keys[0] != keys[1]


def test_changed_eps_traces_a_new_program_never_a_stale_one():
    ids = np.array([5, 9, 2, 7, 11], np.int32)
    m = _build(eps=1e-5)
    before = _next_logits(m, ids)
    n_programs = len(m._gen_cache)
    _set_eps(m, 1e-2)
    after = _next_logits(m, ids)
    assert len(m._gen_cache) == 2 * n_programs
    np.testing.assert_array_equal(after,
                                  _next_logits(_build(eps=1e-2), ids))
    assert np.abs(after - before).max() > 1e-3


def _build_hybrid(seed=3):
    """The hybrid window/full mixture-of-experts LM at a toy size: its
    tree holds the sinks, the router's bias and every expert's
    matrices beside the norms' gains."""
    from singa_tpu.models.hybrid_moe import HybridWindowMoELM

    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    m = HybridWindowMoELM(
        V, d_model=D, num_heads=4, head_dim=12, v_head_dim=8,
        kv_heads_full=1, kv_heads_window=2, window=4, rotary_dim=4,
        layer_pattern=(0, 1, 0), moe_layers=(0, 1, 1), d_ff=64,
        d_ff_expert=16, n_experts=8, experts_per_token=2, held=(2, 2),
        max_len=MAXLEN, init_std=0.3)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                 device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


def _build_shortconv(seed=3):
    """The short-convolution mixture-of-experts LM at a toy size: its
    tree holds the taps, the head norms' gains, the router's bias and
    every expert's matrices, and no head (the embedding again)."""
    from singa_tpu.models.shortconv_moe import ShortConvMoELM

    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    m = ShortConvMoELM(
        V, d_model=D, num_heads=4, kv_heads=2, head_dim=8,
        layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
        d_ff=64, d_ff_expert=16, n_experts=8, experts_per_token=2,
        held=(0, 8), max_len=MAXLEN, init_std=0.3)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                 device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


def _build_chunked(seed=3):
    """The chunk-summary LM at a toy size: its tree holds the pooling
    parameters phi and mu beside the norms' g, and a head of its own."""
    from singa_tpu.models.chunked_attn import ChunkedAttnLM

    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    m = ChunkedAttnLM(V, d_model=D, num_heads=4, head_dim=8, window=4,
                      chunk=2, num_layers=2, d_ff=64, pred_heads=2,
                      max_len=MAXLEN, init_std=0.3)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                 device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


def _build_blocksparse(seed=3):
    """The block-sparse mixture-of-experts LM at a toy size: its tree
    holds the indexer's projection, the per-head norms' gains, the
    router's bias, the held and the shared experts' matrices."""
    from singa_tpu.models.block_sparse_moe import BlockSparseMoELM

    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    m = BlockSparseMoELM(
        V, d_model=D, num_heads=4, kv_heads=2, head_dim=8, rotary_dim=4,
        index_heads=2, index_dim=8, block=4, top_blocks=1, local_blocks=1,
        moe_layers=(0, 1), d_ff=64, d_ff_expert=16, d_ff_shared=16,
        n_experts=8, experts_per_token=2, held=(2, 4), max_len=MAXLEN,
        prefill_block=8, prefill_tile=4, init_std=0.3)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                 device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


def _build_gateddelta(seed=3):
    """The Gated DeltaNet mixture-of-experts LM at a toy size: its tree
    holds the decays' A_log and dt_bias, the convolution's taps, the
    gated norm's weight, the router, the held experts and the gated
    shared expert."""
    from singa_tpu.models.gated_delta_moe import GatedDeltaMoELM

    dev = device.get_default_device()
    dev.SetRandSeed(seed)
    m = GatedDeltaMoELM(
        V, d_model=D, num_layers=2, full_interval=2, num_heads=4,
        kv_heads=2, head_dim=8, rotary_dim=4, lin_k_heads=2, lin_v_heads=4,
        lin_k_dim=8, lin_v_dim=8, d_ff_expert=16, d_ff_shared=16,
        n_experts=8, experts_per_token=2, held=(2, 4), max_len=MAXLEN,
        init_std=0.3)
    m.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                 device=dev)],
              is_train=False, use_graph=False)
    m.eval()
    return m


@pytest.mark.parametrize("norm,quant", [("layer", "off"),
                                        ("layer", "int8"),
                                        ("rms", "off"),
                                        ("hybrid", "off"),
                                        ("shortconv", "off"),
                                        ("chunked", "off"),
                                        ("blocksparse", "off"),
                                        ("gateddelta", "off")])
def test_warmed_engine_counts_no_host_leaf(norm, quant):
    drawn = {"hybrid": _build_hybrid, "shortconv": _build_shortconv,
             "chunked": _build_chunked, "blocksparse": _build_blocksparse,
             "gateddelta": _build_gateddelta}
    m = drawn[norm]() if norm in drawn else _build(norm)
    prompt = np.array([[3, 1, 4]], np.int32)
    device.set_inference_quant(quant)
    dst = stats.decode_stats()
    dst.host_leaves_per_call = -1
    eng = serve.ServingEngine(m, max_sessions=2, max_new_tokens=4,
                              prefill_batch=2, decode_block=2).start()
    try:
        eng.warm_decode(prompt_lens=(3,), max_new_tokens=4)
        got = eng.submit_decode(prompt, 4).result(timeout=60)
    finally:
        eng.stop()
        device.set_inference_quant("off")
    assert stats.cache_stats()["decode"]["host_leaves_per_call"] == 0
    if norm in drawn:
        import jax

        assert all(isinstance(leaf, jax.Array) for leaf in
                   jax.tree_util.tree_leaves(m._decode_params()))
        assert np.asarray(got).shape == (1, 7)
    elif quant == "off":  # a slab row is generate(), bit for bit
        assert np.array_equal(np.asarray(got), m.generate(prompt, 4))
