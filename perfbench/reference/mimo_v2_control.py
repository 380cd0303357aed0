"""The control of the served-token check: the comparison that decides
`correct` in a serving cell of the `mimo_v2` configuration, given a
tier it has to refuse.

    python3 -m perfbench.reference.mimo_v2_control \
        --workload mimo-v2.5-serve-mixedlen --seed <n>

builds the cell's model and engine as the serving driver does, serves
the check's number of streams of the cell's traffic, and puts two sets
of tokens through `compare.served_within_margin` at the configuration's
margin, both judged by the float32 reference along the served
sequences: the served tokens, which have to come out correct, and the
greedy choice of the reference computed one precision below the
configuration's (`check.control.lower`), which has to come out NOT
correct. Exit 0 only if both do. The last line of output is one JSON
object with both readings. Not a cell and not a metric: the margin's
two sides, read through the harness's own comparison
(tests/perfbench/test_perfbench_moe.py runs it at toy size).
"""
import argparse
import json
import sys
import time

import numpy as np

from perfbench.drivers import serve as driver
from perfbench.harness import cell as cell_mod
from perfbench.harness import compare, traffic


def judge(ref, states, seqs, prompt_lens, total_lens, chk, kwargs):
    """{"served": (correct, worst), "control": (correct, worst)} for
    right-padded `seqs` [B, S] of prompt + served reply."""
    lower = ref.lower_precision_choice(states, seqs, chk["control"]["lower"],
                                       **kwargs)
    out = {}
    for name, tokens in (("served", None), ("control", lower)):
        shortfall, _ = ref.served_shortfall(states, seqs, tokens=tokens,
                                            **kwargs)
        out[name] = compare.served_within_margin(
            np.asarray(shortfall), prompt_lens, total_lens, chk["margin"])
    return out


def run(run):
    """Serve `check.streams` requests of the cell's traffic alone and
    judge them one at a time, as the driver's check does."""
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
    out = {"served": [True, 0.0], "control": [True, 0.0]}
    for ids, full in zip(prompts, fulls):
        seqs = np.zeros((1, longest + new), np.int32)
        seqs[0, :len(full)] = full
        for name, (ok, worst) in judge(ref, states, seqs, [len(ids)],
                                       [len(full)], chk, kwargs).items():
            out[name] = [out[name][0] and ok, max(out[name][1], worst)]
    return {"margin": chk["margin"], "lower": chk["control"]["lower"],
            "streams": len(fulls),
            "served_correct": out["served"][0],
            "served_worst": out["served"][1],
            "control_correct": out["control"][0],
            "control_worst": out["control"][1]}


def main(argv=None):
    import jax

    from singa_tpu import device

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell, config, workload = cell_mod.load_cell(args.workload)
    device.use_compile_cache()     # the cell's programs, as run.py keeps them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    result = run(cell_mod.Run(cell=cell, config=config, workload=workload,
                              seconds=0.0, trace=False, seed=args.seed,
                              t_process_start=time.perf_counter()))
    print(json.dumps(result), flush=True)
    return 0 if result["served_correct"] and not result["control_correct"] \
        else 1


if __name__ == "__main__":
    sys.exit(main())
