"""What every language model of the decode tier shares: the compiled
program cache, the AOT route, the sampler, and the three programs
`ServingEngine` dispatches (`decode_step`, `decode_scan`,
`prefill_slab`) as wrappers around a model's own step functions.

A subclass provides its mathematics and states its slab:

  `_norm_eps()`                         every norm's `eps`, static
  `_decode_params()`                    the tree every program receives
  `_slot_step(params, slab, tok, pos)`  one fused step -> (logits,
                                        slab) or (logits, slab, counters)
  `_prefill_rows(params, slab, ids, n_real, slots)` -> (logits, slab)
  `new_slab / grow_slab / slab_dims / slab_bytes / _slab_sig /
  _slab_extra`                          the cache's geometry

`serve.py` asks the model for the geometry and never reads a layer's
head count or axis itself. Every program that takes the slab DONATES it
and updates it where it lies: the caller keeps only the slab a program
returns, and a program that fails after it was dispatched leaves none
(`ServingEngine._slab_lost`). `scan_unroll` says whether a run-ahead
block is a loop or its steps in a row.
"""
from __future__ import annotations

from .. import autograd, model


class DecodeLM(model.Model):
    """Base of `TransformerLM` and `HybridWindowMoELM`."""

    # int32 vector a step may return beside (logits, slab): summed on
    # the host into `stats.cache_stats()["decode"]` under these names
    step_counter_names = ()
    # `lax.scan`'s unroll of a run-ahead block's steps (1: a loop)
    scan_unroll = 1

    def __init_subclass__(cls, **kwargs):
        """A model's counter names read 0 in `cache_stats()["decode"]`
        from its class's definition on, so a snapshot taken before its
        first step already holds them."""
        super().__init_subclass__(**kwargs)
        from .. import stats

        for name in cls.step_counter_names:
            stats.decode_stats().step_counters.setdefault(name, 0)

    def _trace_key(self):
        """What a decode program closes over besides its arguments'
        shapes: the precision policy and the norms' `eps`."""
        return (autograd._policy_key(), self._norm_eps())

    def _program_cache(self):
        """`_gen_cache`: the model's compiled decode-program cache —
        a bounded `stats.TieredLRUCache` sharing the process-wide
        `cache_stats()["decode"]` counters (was an unbounded dict;
        a long-lived server cycling sampling configs and shapes must
        evict, not grow)."""
        from .. import stats as stats_mod

        cache = getattr(self, "_gen_cache", None)
        if cache is None:
            cache = self._gen_cache = stats_mod.TieredLRUCache(
                "decode", stats=stats_mod.decode_stats().cache)
        return cache

    @staticmethod
    def _count_first_trace(fn):
        """Time `fn`'s first invocation (trace + compile + run) into
        the decode CacheStats — the retrace-storm signal for the
        decode tier."""
        import time

        import jax

        from .. import stats as stats_mod

        state = [True]

        def wrapped(*a):
            if state[0]:
                state[0] = False
                t0 = time.perf_counter()
                out = fn(*a)
                jax.block_until_ready(out)
                stats_mod.decode_stats().cache.record_trace(
                    time.perf_counter() - t0)
                return out
            return fn(*a)

        return wrapped

    def _aot_step(self, kind, jitted, args, extras):
        """Route a decode-tier step through the AOT store when armed:
        load the serialized executable (no trace) or trace once +
        publish, falling back to the plain jit on store miss/failure.
        `args` must be the CONCRETE first-call arguments."""
        import jax

        from .. import export_cache

        if not export_cache.active():
            return self._count_first_trace(jitted)
        key, parts = export_cache.step_key(self, None, kind, args,
                                           extras=extras)
        exp = export_cache.load(key)
        if exp is None:
            exp = export_cache.export_and_save(key, parts, jitted,
                                               args)
            if exp is None:
                return self._count_first_trace(jitted)
        return jax.jit(exp.call, donate_argnums=(1,))

    def _slab_program(self, kind, key_, fn, args, extras):
        """The cached executable of one slab-taking program (argument
        1 is the slab, donated)."""
        cache_dict = self._program_cache()
        key_ = key_ + (self._slab_sig(args[1]), self._trace_key())
        hit = cache_dict.get(key_)
        if hit is None:
            import jax

            jitted = jax.jit(fn, donate_argnums=(1,))
            hit = self._aot_step(
                kind, jitted, args,
                extras={**extras, "slab": self._slab_extra(args[1]),
                        "policy": autograd._policy_key()})
            cache_dict[key_] = hit
        return hit

    def _keep_counters(self, out):
        """(result, slab) of a program; its counters vector, where the
        model's step gives one, waits for `take_step_counters`."""
        if len(out) == 3:
            self._step_counters = out[2]
        return out[0], out[1]

    def detach_step_counters(self):
        """The last dispatched program's counters vector, still on the
        device, taken off the model (None where its step counts
        nothing). A caller that dispatches a block behind one it has
        not read back keeps each block's vector beside that block and
        names it after the block's readback: `take_step_counters(vec)`."""
        return self.__dict__.pop("_step_counters", None)

    def take_step_counters(self, vec=None):
        """A program's counters as host ints by name: those of `vec`
        (as `detach_step_counters` gave it), else the last dispatched
        step's (or block's), once; {} for a model whose step counts
        nothing."""
        import numpy as np

        if vec is None:
            vec = self.detach_step_counters()
        if vec is None:
            return {}
        return dict(zip(self.step_counter_names,
                        (int(v) for v in np.asarray(vec))))

    def take_next_tokens(self):
        """The token row the last `decode_scan`'s steps ended on, [B]
        int32 left on the device, once: the `tok` of a block dispatched
        behind that one before its tokens come to the host."""
        return self.__dict__.pop("_next_tokens", None)

    def decode_step(self, params, cache, tok, pos):
        """ONE fused decode step for the serving tier: advance every
        slab row by one token (`tok` [B] int32 at per-row positions
        `pos` [B] int32), returning (next-token logits [B, V], new
        cache). Compiled once per slab shape — the one warm executable
        continuous batching dispatches every step — and AOT-exported
        through export_cache when the store is armed."""
        def slot_step(p, c, t, po):   # the module's name in a trace
            return self._slot_step(p, c, t, po)

        args = (params, list(cache), tok, pos)
        fn = self._slab_program("decode_step", ("slot_step",), slot_step,
                                args, {})
        return self._keep_counters(fn(*args))

    def decode_scan(self, params, cache, tok, pos, k):
        """`k` GREEDY fused decode steps in ONE program (`_slot_step`
        + in-graph argmax, under `lax.scan` where `k` > 1). The scan's
        cache carry is the donated slab, updated in place step after
        step, so a block costs its steps and one dispatch, one readback
        of [k, B] tokens instead of k of [B, V] logits: that, and not a
        saved copy, is what a block is for. `k` == 1 is the token
        program of a single step: `decode_step` and the argmax in a
        straight line, no loop around them (a loop hoists what a step
        fuses: GPT-2's block holds its weights converted to bfloat16),
        so the device pays `decode_step` and an argmax and the host
        reads [1, B] int32 where it read [B, V] logits. In-graph
        `jnp.argmax` is the exact greedy program
        `generate()` scans with (and equals host `np.argmax` on
        identical logits bits — both first-max-wins, NaN the largest),
        so a block decodes bit-identically to k single steps. Returns
        (toks [k, B] — one sampled token per step per row, new
        cache); the carry's last token row, `toks[k - 1]` as an output
        of its own, waits on the device for `take_next_tokens`, so a
        block can follow this one with no program in between. The
        caller only dispatches a block when no session joins, leaves,
        expires, or samples within it."""
        import jax
        import jax.numpy as jnp

        def greedy_step(p, c, t, po):
            logits, c, *counters = self._slot_step(p, c, t, po)
            return jnp.argmax(logits, -1).astype(jnp.int32), c, counters

        def scan_k(p, c, t, po):
            if int(k) == 1:
                t2, c, counters = greedy_step(p, c, t, po)
                return (t2[None], c, t2, *counters)

            def body(carry, _):
                c, t, po = carry
                t2, c, counters = greedy_step(p, c, t, po)
                return (c, t2, po + 1), (t2, *counters)

            (c, t, _po), (toks, *counters) = jax.lax.scan(
                body, (c, t, po), None, length=int(k),
                unroll=self.scan_unroll)
            return (toks, c, t, *(v.sum(0) for v in counters))

        # a trace names the module by this: the block's steps with it
        scan_k.__name__ = f"slot_scan_{int(k)}"
        args = (params, list(cache), tok, pos)
        fn = self._slab_program("decode_scan", ("slot_scan", int(k)),
                                scan_k, args, {"block": int(k)})
        toks, slab, self._next_tokens, *counters = fn(*args)
        return self._keep_counters((toks, slab, *counters))

    def prefill_slab(self, params, slab, ids, n_real, slots):
        """Prefill a COHORT of bucket-padded prompts and scatter their
        state into slab rows `slots` in a single program: each row
        reads its own last real token's logits (`n_real` [Bp] int32).
        Param streaming — the dominant prefill cost on memory-bound
        hosts — is paid once per cohort instead of once per session,
        the same amortization the fused decode step applies. `slots`
        [Bp] int32 is traced — one executable per (Bp, Pb) serves
        every row assignment; a row whose slot is out of bounds writes
        nothing. Returns (logits [Bp, V], new slab)."""
        def prefill_rows(p, sl, i, n, s):
            return self._prefill_rows(p, sl, i, n, s)

        args = (params, list(slab), ids, n_real, slots)
        fn = self._slab_program(
            "prefill_slab", ("prefill_slab", tuple(ids.shape)),
            prefill_rows, args, {"prompt_bucket": list(ids.shape)})
        return fn(*args)

    def sample_fn(self, temperature, top_k):
        """The EXACT sampling program generate() compiles (argmax when
        temperature == 0, else temperature-scaled top-k categorical)
        as a standalone jitted fn `(logits [B, V], key) -> tok [B]`.
        The serving tier samples each session host-side with the same
        `jax.random.split` sequence generate() traces, keeping
        streamed tokens bit-identical to the sequential path."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        key_ = ("sample", float(temperature), int(top_k),
                autograd._policy_key())
        cache_dict = self._program_cache()
        fn = cache_dict.get(key_)
        if fn is not None:
            return fn

        def sample(logits, key):
            if temperature == 0.0:
                return jnp.argmax(logits, -1).astype(jnp.int32)
            z = logits / temperature
            if top_k > 0:
                k = min(int(top_k), int(logits.shape[-1]))
                kth = lax.top_k(z, k)[0][..., -1:]
                z = jnp.where(z < kth, -jnp.inf, z)
            return jax.random.categorical(key, z).astype(jnp.int32)

        fn = jax.jit(sample)
        cache_dict[key_] = fn
        return fn
