"""The control of the served-token check for the `qwen3-next-80b-a3b`
configuration: the comparison that decides `correct` in its serving
cell, given what it has to refuse, and the reading of the matrix states
the tokens cannot see.

    python3 -m perfbench.reference.qwen3_next_control \
        --workload qwen3-next-serve-decode256 --seed <n> \
        [--fault state_bfloat16|state_kept]

serves the check's number of streams of the cell's traffic and puts two
sets of tokens through `compare.served_within_margin` at the
configuration's margin, both judged by `qwen3_next_ref.py` in float32
along the served sequences: the served tokens, which have to come out
correct, and the greedy choice of the same reference with every matrix
and every product's input rounded to `check.control.lower`
(float8_e4m3fn under the configuration's bfloat16), which has to come
out NOT correct (`mimo_v2_control.run`). Before it, the STATE READING
(`state_reading`): how far the matrix states a slab row holds after a
whole rung of positions lie from the reference recurrence's, the
largest over the Gated DeltaNet layers, which has to stay within
`check.state_limit`. Exit 0 only if all three hold; the
last line of output is one JSON object with the readings.

`--fault <name>` plants a fault of the recurrent state's rules in the
program before it is built and serves through it: `state_bfloat16` (the
slab holds the matrix states in bfloat16: each step reads and writes
them rounded), `state_kept` (a prefill leaves a slot's matrix states as
it found them: a new session starts from the last one's, zero in a
fresh slot). Exit 0 only if the state reading passes its limit (then
nothing is served) or the served tokens come out NOT correct.
"""
import argparse
import gc
import json
import sys
import time

import numpy as np

from perfbench.harness import cell as cell_mod
from perfbench.harness import traffic
from perfbench.reference import mimo_v2_control

FAULTS = ("state_bfloat16", "state_kept")


def plant(fault):
    """Break one rule of the recurrent state in `GatedDeltaMoELM`, for
    this process."""
    from singa_tpu.models.gated_delta_moe import GatedDeltaMoELM as cls

    if fault == "state_bfloat16":
        inner = cls.new_slab

        def new_slab(self, params, slots, seq, device):
            import jax.numpy as jnp

            return [{**c, "S": c["S"].astype(jnp.bfloat16)} if "S" in c
                    else c for c in inner(self, params, slots, seq, device)]
        cls.new_slab = new_slab
    elif fault == "state_kept":
        inner = cls._prefill_rows

        def _prefill_rows(self, params, slab, ids, n_real, slots):
            logits, new = inner(self, params, slab, ids, n_real, slots)
            return logits, [{**n, "S": c["S"]} if "S" in c else n
                            for c, n in zip(slab, new)]
        cls._prefill_rows = _prefill_rows
    else:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")


def state_reading(run):
    """|S - S_ref| / |S_ref| (Frobenius, over a layer's value heads),
    the largest over the check's streams, for each Gated DeltaNet layer:
    S the matrix states a slab row holds after `longest + new` positions
    of the cell's traffic, S_ref the reference recurrence's over the
    same ids. Each stream is a request's prompt prefilled into a slab of
    one row through the engine's program (`prefill_slab`), then the
    program's greedy tokens fed back one position at a time through its
    token program (`decode_scan`, one step) to the end of the longest
    context the cell serves: the states carry what every step's reading
    and writing of them left. What is judged is the largest over the
    layers; the first layer's alone reads the state's own arithmetic
    (its inputs are the embeddings both sides share), a deeper layer's
    adds the drift of every layer under it."""
    import jax
    import jax.numpy as jnp

    from singa_tpu import device, tensor

    sec = run.config["serve"]
    cell_mod.set_policies(sec)
    dev = device.create_tpu_device()
    dev.SetRandSeed(run.seed)
    model = cell_mod.build(run.config["builder"])
    model.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                     device=dev)],
                  is_train=False, use_graph=False)
    model.eval()
    params = model._decode_params()
    where = jax.tree_util.tree_leaves(params)[0].sharding

    def put(a):
        return jax.device_put(np.asarray(a, np.int32), where)

    ref = cell_mod.module("reference", run.config["reference"]["module"])
    kwargs = run.config["reference"].get("kwargs", {})
    states = {k: v.data for k, v in model.get_states().items()}
    _, longest, new = traffic.limits(run.workload)
    total = longest + new
    bucket = 1 << (longest - 1).bit_length()
    worst = []
    for c in range(sec["check"]["streams"]):
        req = traffic.closed_request(run.workload, run.seed, c, 1)
        ids = traffic.prompt_ids(req, model.vocab_size)
        row = np.zeros((1, bucket), np.int32)
        row[0, :len(ids)] = ids
        slab = model.new_slab(params, 1, 1 << (total - 1).bit_length(), where)
        logits, slab = model.prefill_slab(params, slab, put(row),
                                          put([len(ids)]), put([0]))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        fed = []
        for p in range(len(ids), total):
            fed.append(tok)
            _, slab = model.decode_scan(params, slab, tok, put([p]), 1)
            tok = model.take_next_tokens()
        seq = np.concatenate([ids, np.asarray(jnp.concatenate(fed))])
        worst.append([float(jnp.linalg.norm(got - want)
                            / jnp.linalg.norm(want))
                      for got, want in zip(
                          [c["S"][0].astype(jnp.float32)
                           for c in slab if "S" in c],
                          ref.recurrent_states(states, seq, **kwargs))])
    del model, params, states, slab
    gc.collect()
    by_layer = [max(col) for col in zip(*worst)]
    return {"state_positions": total,
            "state_limit": sec["check"]["state_limit"],
            "state_worst": max(by_layer), "state_worst_by_layer": by_layer}


def run(run, fault=None):
    """`state_reading`, then `mimo_v2_control.run` (serve
    `check.streams` requests of the cell's traffic alone, judge each as
    the driver's check does), through a planted fault where one is
    named: a fault the state reading already tells from the program's
    is served no further."""
    if fault:
        plant(fault)
    out = state_reading(run)
    if not fault or out["state_worst"] <= out["state_limit"]:
        out.update(mimo_v2_control.run(run))
    if fault:
        out["fault"] = fault
    return out


def main(argv=None):
    import jax

    from singa_tpu import device

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault", choices=FAULTS)
    args = ap.parse_args(argv)
    cell, config, workload = cell_mod.load_cell(args.workload)
    device.use_compile_cache()     # the cell's programs, as run.py keeps them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    result = run(cell_mod.Run(cell=cell, config=config, workload=workload,
                              seconds=0.0, trace=False, seed=args.seed,
                              t_process_start=time.perf_counter()),
                 fault=args.fault)
    print(json.dumps(result), flush=True)
    state_ok = result["state_worst"] <= result["state_limit"]
    if args.fault:
        return 0 if not state_ok or not result["served_correct"] else 1
    return 0 if (result["served_correct"] and state_ok
                 and not result["control_correct"]) else 1


if __name__ == "__main__":
    sys.exit(main())
