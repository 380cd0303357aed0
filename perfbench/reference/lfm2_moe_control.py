"""The control of the served-token check for the `lfm2_moe`
configuration: the comparison that decides `correct` in its serving
cell, given what it has to refuse, and a witness of where the served
tokens' distance from the float32 reference comes from.

    python3 -m perfbench.reference.lfm2_moe_control \
        --workload lfm2-24b-a2b-serve-decode128 --seed <n> \
        [--witness bfloat16] [--fault state_kept|state_stuck]

serves the check's number of streams of the cell's traffic and puts two
sets of tokens through `compare.served_within_margin` at the
configuration's margin, both judged by `lfm2_moe_ref.py` in float32
along the served sequences: the served tokens, which have to come out
correct, and the greedy choice of the same reference with every matrix
but the router's and every product's input rounded to
`check.control.lower` (float8_e4m3fn under the configuration's
bfloat16), which has to come out NOT correct (`mimo_v2_control.judge`).
Exit 0 only if both do; the last line of output is one JSON object
with the readings.

`--witness <dtype>` adds, along the same served sequences and through
the same comparison: the greedy choice of the reference rounded to the
configuration's own dtype (no cache, no state, no kernel: what rounding
alone costs), the same with every routed layer GIVEN the float32
reference's chosen experts (what is left of it once no expert is
swapped), the greedy choice of the program's own eval `forward()` (the
same stack with no cache and no state: what the served path's context,
state and step programs add to it), and for each routed layer at how
many judged positions the chosen experts differ from the float32
reference's.

`--fault <name>` plants a fault of the state's rules in the program
before it is built and serves through it: `state_kept` (a prefill
leaves a slot's states as it found them: not reset, not written) or
`state_stuck` (a step reads the state and never writes it). Exit 0 only
if the served tokens come out NOT correct.
"""
import argparse
import json
import sys
import time
from unittest import mock

import numpy as np

from perfbench.drivers import serve as driver
from perfbench.harness import cell as cell_mod
from perfbench.harness import compare, traffic
from perfbench.reference.mimo_v2_control import judge

FAULTS = ("state_kept", "state_stuck")


def plant(fault):
    """Break one rule of the convolution state in `ShortConvMoELM`,
    for this process."""
    from singa_tpu.models import shortconv_moe

    cls = shortconv_moe.ShortConvMoELM

    def states_as_found(self, slab, new):
        return [old if kind == shortconv_moe.CONV else n
                for kind, old, n in zip(self.layer_types, slab, new)]

    if fault == "state_kept":
        inner = cls._prefill_rows

        def prefill_rows(self, params, slab, ids, n_real, slots):
            logits, new = inner(self, params, slab, ids, n_real, slots)
            return logits, states_as_found(self, slab, new)

        cls._prefill_rows = prefill_rows
    elif fault == "state_stuck":
        inner = cls._slot_step

        def slot_step(self, params, slab, tok, pos):
            logits, new, counters = inner(self, params, slab, tok, pos)
            return logits, states_as_found(self, slab, new), counters

        cls._slot_step = slot_step
    else:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")


def program_choice(model):
    """ids [B, S] -> (greedy next tokens [B, S-1] of the program's eval
    forward, its routed layers' chosen experts [layers, B, S, k]). The
    router's choice is computed again from the routed layer's own
    arguments, as `routed_experts` computes it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from singa_tpu.models import drawn_lm

    real = drawn_lm.routed_experts

    def fn(params, ids):
        taken = []

        def tapped(ffn, x, prec, **kw):
            sig = jax.nn.sigmoid(jnp.matmul(
                x.astype(jnp.float32), ffn["W_r"].astype(jnp.float32),
                precision=lax.Precision.HIGHEST))
            taken.append(lax.top_k(sig + ffn["b"],
                                   kw["experts_per_token"])[1])
            return real(ffn, x, prec, **kw)

        with mock.patch.object(drawn_lm, "routed_experts", tapped):
            lg = model._eval_logits(params, ids)
        return (lg[:, :-1].argmax(-1),
                jnp.stack(taken).reshape(len(taken), *ids.shape, -1))

    return jax.jit(fn)


def witness(ref, model, program, states, seqs, p, n, chk, kwargs, lower):
    """One served sequence's witness readings (see the module's
    words): {name: (correct, worst)} and {name: [differing judged
    positions a routed layer]}; input positions p-1 .. n-2 are judged."""
    exact, rounded, free, forced = ref.routing_witness(states, seqs, lower,
                                                       **kwargs)
    own, routes = program(model._decode_params(), seqs)
    worst = {}
    for name, tokens in (("reference_in_" + lower, free),
                         ("reference_in_" + lower + "_routed_as_float32",
                          forced), ("program_eval", own)):
        shortfall, _ = ref.served_shortfall(states, seqs, tokens=tokens,
                                            **kwargs)
        worst[name] = compare.served_within_margin(
            np.asarray(shortfall), [p], [n], chk["margin"])
    served, _ = ref.served_shortfall(states, seqs, **kwargs)
    served = np.asarray(served)[0, p - 1:n - 1]

    def differ(a):
        a, b = (np.sort(np.asarray(t)[:, 0, p - 1:n - 1], -1)
                for t in (a, exact))
        return (a != b).any(-1)                     # [layers, judged]

    swapped = differ(routes)
    return worst, {
        "program": swapped.sum(1), "reference_in_" + lower:
        differ(rounded).sum(1)}, {
        "judged": n - p,
        "served_equals_program_eval": int(
            (np.asarray(own)[0, p - 1:n - 1] == seqs[0, p:n]).sum()),
        "judged_with_a_swap": int(swapped.any(0).sum()),
        "served_worst_with_a_swap": float(
            served[swapped.any(0)].max(initial=0.0)),
        "served_worst_with_none": float(
            served[~swapped.any(0)].max(initial=0.0))}


def run(run, lower=None, fault=None):
    """Serve `check.streams` requests of the cell's traffic alone and
    judge them one at a time, as the driver's check does."""
    if fault:
        plant(fault)
    model, engine = driver.build(run)
    chk = run.config["serve"]["check"]
    try:
        reqs = [traffic.closed_request(run.workload, run.seed, c, 1)
                for c in range(chk["streams"])]
        prompts = [traffic.prompt_ids(r, model.vocab_size) for r in reqs]
        replies = [engine.submit_decode(ids, r.n_new, temperature=0.0,
                                        top_k=0, seed=r.index)
                   for ids, r in zip(prompts, reqs)]
        fulls = [np.asarray(rep.result(timeout=600))[0] for rep in replies]
    finally:
        engine.stop(drain=False)
    ref = cell_mod.module("reference", run.config["reference"]["module"])
    kwargs = run.config["reference"].get("kwargs", {})
    states = {k: v.data for k, v in model.get_states().items()}
    _, longest, new = traffic.limits(run.workload)
    program = program_choice(model) if lower else None
    worst, swaps, counts = {}, {}, {}
    for ids, full in zip(prompts, fulls):
        seqs = np.zeros((1, longest + new), np.int32)
        seqs[0, :len(full)] = full
        p, n = len(ids), len(full)
        got = judge(ref, states, seqs, [p], [n], chk, kwargs)
        if lower:
            more, swapped, count = witness(ref, model, program, states, seqs,
                                           p, n, chk, kwargs, lower)
            got.update(more)
            for k, v in swapped.items():
                swaps[k] = swaps.get(k, 0) + v
            for k, v in count.items():
                counts[k] = (max(counts.get(k, 0.0), v) if "worst" in k
                             else counts.get(k, 0) + v)
        for name, (ok, w) in got.items():
            was = worst.get(name, (True, 0.0))
            worst[name] = (was[0] and ok, max(was[1], w))
    out = {"margin": chk["margin"], "lower": chk["control"]["lower"],
           "streams": len(fulls)}
    for name, (ok, w) in worst.items():
        out[name + "_correct"], out[name + "_worst"] = ok, w
    if lower:
        out.update(counts)
        out["judged_where_a_routed_layer_chose_other_experts"] = {
            k: [int(c) for c in v] for k, v in swaps.items()}
    if fault:
        out["fault"] = fault
    return out


def main(argv=None):
    import jax

    from singa_tpu import device

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--witness", metavar="DTYPE")
    ap.add_argument("--fault", choices=FAULTS)
    args = ap.parse_args(argv)
    cell, config, workload = cell_mod.load_cell(args.workload)
    device.use_compile_cache()     # the cell's programs, as run.py keeps them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    result = run(cell_mod.Run(cell=cell, config=config, workload=workload,
                              seconds=0.0, trace=False, seed=args.seed,
                              t_process_start=time.perf_counter()),
                 lower=args.witness, fault=args.fault)
    print(json.dumps(result), flush=True)
    if args.fault:
        return 0 if not result["served_correct"] else 1
    return 0 if result["served_correct"] and not result["control_correct"] \
        else 1


if __name__ == "__main__":
    sys.exit(main())
