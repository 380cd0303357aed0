"""The control of the served-token check for the `evabyte`
configuration: the comparison that decides `correct` in its serving
cell, given what it has to refuse.

    python3 -m perfbench.reference.evabyte_control \
        --workload evabyte-serve-longctx32 --seed <n> \
        [--fault summaries_seen_early|summary_unwritten]

serves the check's number of streams of the cell's traffic and puts two
sets of tokens through `compare.served_within_margin` at the
configuration's margin, both judged by `evabyte_ref.py` in float32
along the served sequences: the served tokens, which have to come out
correct, and the greedy choice of the same reference with every matrix
and every product's input rounded to `check.control.lower`
(float8_e4m3fn under the configuration's bfloat16), which has to come
out NOT correct (`mimo_v2_control.run`). Exit 0 only if both do; the
last line of output is one JSON object with the readings.

`--fault <name>` plants a fault of the cache's rules in the program
before it is built and serves through it: `summaries_seen_early` (a
step's query also sees the summaries of the chunks already complete in
its OWN block, whose positions it sees exactly: counted twice) or
`summary_unwritten` (a step never writes a summary: the entries of the
chunks that close during decoding stay as the prefill left them, zero).
Exit 0 only if the served tokens come out NOT correct. Both show only
once a session has decoded across a chunk's end and, for the second,
across a block boundary after it: the control's streams are whole
replies of the cell's traffic (256-2,048 positions after prompts of
2,048-12,288), of which most cross one.
"""
import argparse
import json
import sys
import time

from perfbench.harness import cell as cell_mod
from perfbench.reference import mimo_v2_control

FAULTS = ("summaries_seen_early", "summary_unwritten")


def plant(fault):
    """Break one rule of the summaries in `ChunkedAttnLM`, for this
    process."""
    from singa_tpu.models.chunked_attn import ChunkedAttnLM as cls

    if fault == "summaries_seen_early":
        cls._seen_summaries = lambda self, pos: pos // self.chunk
    elif fault == "summary_unwritten":
        inner = cls._slot_step

        def slot_step(self, params, slab, tok, pos):
            logits, new, counters = inner(self, params, slab, tok, pos)
            return logits, [{**n, "sk": c["sk"], "sv": c["sv"]}
                            for c, n in zip(slab, new)], counters

        cls._slot_step = slot_step
    else:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")


def run(run, fault=None):
    """`mimo_v2_control.run` (serve `check.streams` requests of the
    cell's traffic alone, judge each as the driver's check does),
    through a planted fault where one is named."""
    if fault:
        plant(fault)
    out = mimo_v2_control.run(run)
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
    if args.fault:
        return 0 if not result["served_correct"] else 1
    return 0 if result["served_correct"] and not result["control_correct"] \
        else 1


if __name__ == "__main__":
    sys.exit(main())
