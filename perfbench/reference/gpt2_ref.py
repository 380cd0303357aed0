"""Plain reference of the GPT-2 block (Radford et al. 2019; the
published `modeling_gpt2`): learned positions, pre-norm blocks of
multi-head causal attention and a 4x MLP, final LayerNorm, head tied
to the token embedding. float32 `jax.numpy`, matmuls at "highest"
precision, no kernels, no cache, no batching tricks. It shares no code
with the program: it is given the program's weights by name
(`Model.get_states()`) and nothing else.

Departures from the published block, as the program has them (listed
under `assumed` in configs/gpt2.json): the MLP activation is the exact
erf GELU (`autograd.Gelu`), where GPT-2 uses the tanh approximation
`gelu_new`; q, k, v are three [d, d] projections, not one fused
`c_attn` (same mathematics).
"""
import functools
import math

import jax
import jax.numpy as jnp


def weights(states, prefix="TransformerLM"):
    """The program's arrays by dotted name -> a plain nested dict."""
    def g(name):
        return jnp.asarray(states[f"{prefix}.{name}"], jnp.float32)

    n_layers = 1 + max(int(k.split(".")[2][1:]) for k in states
                       if k.startswith(f"{prefix}.blocks.l"))
    blocks = []
    for i in range(n_layers):
        b = f"blocks.l{i}"
        blocks.append({k: g(f"{b}.{v}") for k, v in {
            "ln1_g": "ln1.gamma", "ln1_b": "ln1.beta",
            "wq": "attn.q_proj.W", "bq": "attn.q_proj.b",
            "wk": "attn.k_proj.W", "bk": "attn.k_proj.b",
            "wv": "attn.v_proj.W", "bv": "attn.v_proj.b",
            "wo": "attn.o_proj.W", "bo": "attn.o_proj.b",
            "ln2_g": "ln2.gamma", "ln2_b": "ln2.beta",
            "w1": "fc1.W", "b1": "fc1.b", "w2": "fc2.W", "b2": "fc2.b",
        }.items()})
    return {"wte": g("embed.W"), "wpe": g("pos_embed.W"), "blocks": blocks,
            "lnf_g": g("ln_f.gamma"), "lnf_b": g("ln_f.beta")}


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def hidden(w, ids, n_head, eps=1e-5):
    """[B, S] token ids -> [B, S, d] final hidden states."""
    B, S = ids.shape
    h = w["wte"][ids] + w["wpe"][jnp.arange(S)]
    d = h.shape[-1]
    dh = d // n_head
    causal = jnp.tril(jnp.ones((S, S), bool))

    def heads(t):
        return t.reshape(B, S, n_head, dh).transpose(0, 2, 1, 3)

    for blk in w["blocks"]:
        x = _ln(h, blk["ln1_g"], blk["ln1_b"], eps)
        q, k, v = (heads(x @ blk[m] + blk[c]) for m, c in
                   (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
        s = jnp.where(causal, s, -jnp.inf)
        a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        h = h + a.transpose(0, 2, 1, 3).reshape(B, S, d) @ blk["wo"] \
            + blk["bo"]
        x = _ln(h, blk["ln2_g"], blk["ln2_b"], eps)
        h = h + jax.nn.gelu(x @ blk["w1"] + blk["b1"],
                            approximate=False) @ blk["w2"] + blk["b2"]
    return _ln(h, w["lnf_g"], w["lnf_b"], eps)


@functools.partial(jax.jit, static_argnames="n_head")
def logits(states, ids, n_head):
    """[B, S, vocab] next-token logits, float32."""
    with jax.default_matmul_precision("highest"):
        w = weights(states)
        return hidden(w, jnp.asarray(ids), n_head) @ w["wte"].T


def served_shortfall(states, seqs, n_head):
    """For each sequence (prompt + served reply, right-padded to one
    length) and each next token, how far the reference's logit of that
    token lies under the reference's own best logit at that position:
    (shortfall [B, S-1], std of the logits). Row b column t judges
    token seqs[b, t+1]; the caller masks the served positions. Causal,
    so the padding changes nothing before it. Computed on the device;
    only [B, S] floats come back."""
    return _shortfall(states, jnp.asarray(seqs), n_head)


@functools.partial(jax.jit, static_argnums=2)
def _shortfall(states, ids, n_head):
    lg = logits(states, ids, n_head)[:, :-1]            # judges ids[:, 1:]
    got = jnp.take_along_axis(lg, ids[:, 1:, None], -1)[..., 0]
    return lg.max(-1) - got, jnp.std(lg)
