"""The control of the served-token check for the `minimax-m3`
configuration: the comparison that decides `correct` in its serving
cell, given what it has to refuse.

    python3 -m perfbench.reference.minimax_m3_control \
        --workload minimax-m3-serve-longctx20 --seed <n> \
        [--fault select_recent|blockkey_stale|no_select]
        [--witness float32|bfloat16]

serves the check's number of streams of the cell's traffic and puts two
sets of tokens through `compare.served_within_margin` at the
configuration's margin, both judged by `minimax_m3_ref.py` in float32
along the served sequences: the served tokens, which have to come out
correct, and the greedy choice of the same reference with every matrix
and every product's input rounded to `check.control.lower`
(float8_e4m3fn under the configuration's bfloat16), which has to come
out NOT correct (as `mimo_v2_control` does). Beside them it reads the
SELECTION AGREEMENT: along the same streams, the share of (position,
layer, group) whose set of picked blocks differs between the program
and the reference, on both of the program's paths: the prefill's
selection over each whole sequence (`BlockSparseMoELM.picks`) and the
decode step's along each reply, from the pooled keys a slab row holds
(`decode_picks`).
Exit 0 only if the served tokens are correct and the control's are
not; the last line of output is one JSON object with the readings.

`--fault <name>` plants a fault of the selection's rules in the program
before it is built and serves through it: `select_recent` (the `top`
blocks nearest the query, not the indexer's), `blockkey_stale` (a decode
step never updates the pooled keys: a block completed while decoding
keeps the lowest value, or what the prefill left), `no_select` (plain
causal attention over every block). Exit 0 only if the served tokens
come out NOT correct; where they come out correct all the same, the
line carries the fault's selection agreement, the reading that tells
it from the served side's.

`--witness <dtype>` serves nothing: it draws the program in `dtype`,
cut to its first two layers (the dense one and a routed one), in
float32 with every product at "highest",
and reads the selection agreement of both paths along sequences of the
cell's lengths; float32 leaves program and reference the same
arithmetic in another order, so the bfloat16 reading less the float32
one is what the configuration's precision adds. Exit 0; the line
carries the readings.
"""
import argparse
import dataclasses
import json
import sys
import time

import numpy as np

from perfbench.drivers import serve as driver
from perfbench.harness import cell as cell_mod
from perfbench.harness import compare, traffic

FAULTS = ("select_recent", "blockkey_stale", "no_select")


def plant(fault):
    """Break one rule of the selection in `BlockSparseMoELM`, for this
    process."""
    from singa_tpu.models.block_sparse_moe import BlockSparseMoELM as cls

    if fault == "select_recent":
        def selection(self, sig, c):
            import jax.numpy as jnp

            b = jnp.arange(sig.shape[-1])
            return (b == 0) | ((b <= c[..., None])
                               & (b > c[..., None] - self.local_blocks
                                  - self.top_blocks))
        cls._selection = selection
    elif fault == "blockkey_stale":
        inner = cls._slot_step

        def slot_step(self, params, slab, tok, pos):
            logits, new, counters = inner(self, params, slab, tok, pos)
            return logits, [{**n, "kp": c["kp"]}
                            for c, n in zip(slab, new)], counters
        cls._slot_step = slot_step
    elif fault == "no_select":
        def selection(self, sig, c):
            import jax.numpy as jnp

            return jnp.arange(sig.shape[-1]) <= c[..., None]
        init = cls.__init__

        def __init__(self, *a, **kw):
            init(self, *a, **kw)
            self.top_blocks = 1 << 20     # the ids hold every block
        cls._selection, cls.__init__ = selection, __init__
    else:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")


def decode_picks(model, prompts, fulls):
    """Each layer's selected block ids [B, n, G, width] as the DECODE
    step picks them along the served replies, n the longest reply less
    one: every prompt prefilled into a slot of its own through the
    engine's program (`prefill_slab`), then the reply's tokens fed back
    one step at a time through the fused step (`_slot_step`, its pooled
    keys updated by the running max), the ids taken where the step
    computes them. Row b at step i is the query at position
    len(prompts[b]) + i; a row whose reply has ended repeats its last
    position, and what it picks there is not read."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    params = model._decode_params()
    device = jax.tree_util.tree_leaves(params)[0].sharding

    def put(a):
        return jax.device_put(np.asarray(a, np.int32), device)

    B, N = len(prompts), model.block
    rung = max(1 << (max(map(len, fulls)) - 1).bit_length(), N)
    slab = model.new_slab(params, B, rung, device)
    for b, ids in enumerate(prompts):
        row = np.zeros((1, max(1 << (len(ids) - 1).bit_length(), N)))
        row[0, :len(ids)] = ids
        _, slab = model.prefill_slab(params, slab, put(row),
                                     put([len(ids)]), put([b]))
    n = max(len(f) - len(p) for p, f in zip(prompts, fulls)) - 1
    tok, pos = np.zeros((n, B), np.int32), np.zeros((n, B), np.int32)
    for b, (ids, full) in enumerate(zip(prompts, fulls)):
        at = np.minimum(len(ids) + np.arange(n), len(full) - 2)
        tok[:, b], pos[:, b] = full[at], at

    def steps(params, slab, tok, pos):
        def step(slab, x):
            seen = []
            pick = type(model).selected_ids
            model.selected_ids = lambda mask: (
                seen.append(pick(model, mask)) or seen[-1])
            try:
                _, slab, _ = model._slot_step(params, slab, *x)
            finally:
                del model.selected_ids
            return slab, jnp.stack([ids for ids, _ in seen], 1)
        return lax.scan(step, slab, (tok, pos))[1]    # [n, B, L, G, w]

    got = np.asarray(jax.jit(steps, donate_argnums=1)(
        params, slab, put(tok), put(pos)))
    return [got[:, :, li].swapaxes(0, 1) for li in range(got.shape[2])]


def agreement(run, model, prompts, fulls):
    """Share of (position, group) along the served sequences whose
    picked blocks differ between the program and the reference, a
    layer at a time, by path: `prefill` (the prefill's selection over
    each whole sequence, `BlockSparseMoELM.picks`: each sequence padded
    to the cell's longest, one executable a side, read up to its own
    length) and `decode` (the decode step's along each reply,
    `decode_picks`). Returns {path: [share a layer]}."""
    from singa_tpu import tensor

    ref = cell_mod.module("reference", run.config["reference"]["module"])
    kwargs = run.config["reference"].get("kwargs", {})
    states = {k: v.data for k, v in model.get_states().items()}
    _, longest, new = traffic.limits(run.workload)
    stepped = decode_picks(model, prompts, fulls)
    differ = {"prefill": [], "decode": []}
    for b, (ids, full) in enumerate(zip(prompts, fulls)):
        seq = np.zeros((1, longest + new), np.int32)
        seq[0, :len(full)] = full
        mine = model.picks(tensor.from_numpy(seq))
        theirs = [np.asarray(r) for r in ref.picks(states, seq, **kwargs)]
        differ["prefill"].append([ref.selection_disagreement(
            [np.asarray(p)[:, :len(full)]], [r[:, :len(full)]])
            for p, r in zip(mine, theirs)])
        at = slice(len(ids), len(full) - 1)
        n = at.stop - at.start
        differ["decode"].append([ref.selection_disagreement(
            [d[b:b + 1, :n]], [r[:, at]]) for d, r in zip(stepped, theirs)])
    return {path: [float(v) for v in np.mean(rows, 0)]
            for path, rows in differ.items()}


def witness(run, dtype, layers=2):
    """The selection agreement at the cell's widths with the PROGRAM
    computing in `dtype` (its parameters drawn in it; in float32 every
    product at "highest", in bfloat16 at the configuration's precision),
    cut to its first `layers` layers (float32 weights of the
    whole cut would not fit beside a check): along `check.streams`
    sequences of the cell's lengths (a request's prompt, then random
    ids for its reply), prefill and decode path against the reference
    over the same layers. Float32 leaves the two sides the same
    arithmetic in another order, so what the configuration's bfloat16
    adds to the disagreement is the difference of the two readings."""
    import copy

    from singa_tpu import device, tensor

    config = copy.deepcopy(run.config)
    kw, ref_kw = config["builder"]["kwargs"], config["reference"]["kwargs"]
    kw["param_dtype"] = config["serve"]["compute_dtype"] = dtype
    if dtype == "float32":     # the chip's default rounds to bfloat16
        config["serve"]["matmul_precision"] = "highest"
    kw["moe_layers"] = ref_kw["moe_layers"] = kw["moe_layers"][:layers]
    cell_mod.set_policies(config["serve"])
    dev = device.create_tpu_device()
    dev.SetRandSeed(run.seed)
    model = cell_mod.build(config["builder"])
    model.compile([tensor.from_numpy(np.zeros((1, 4), np.int32),
                                     device=dev)],
                  is_train=False, use_graph=False)
    model.eval()
    rng = np.random.default_rng(run.seed)
    prompts, fulls = [], []
    for c in range(config["serve"]["check"]["streams"]):
        req = traffic.closed_request(run.workload, run.seed, c, 1)
        ids = traffic.prompt_ids(req, model.vocab_size)
        prompts.append(ids)
        fulls.append(np.concatenate([ids, rng.integers(
            0, model.vocab_size, req.n_new, dtype=np.int32)]))
    by_path = agreement(dataclasses.replace(run, config=config), model,
                        prompts, fulls)
    return {"witness": dtype, "layers": layers, "streams": len(fulls),
            **{f"selection_disagreement_{path}_by_layer": v
               for path, v in by_path.items()}}


def run(run, fault=None):
    """Serve `check.streams` requests of the cell's traffic alone and
    judge them one at a time, as the driver's check does
    (`mimo_v2_control`): the served tokens, and without a fault the
    control's choice beside them; then the selection agreement along
    the same streams, without a fault always, through one only where
    the tokens came out correct."""
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
        fulls = [np.asarray(rep.result(timeout=900))[0] for rep in replies]
    finally:
        engine.stop(drain=False)
    ref = cell_mod.module("reference", run.config["reference"]["module"])
    kwargs = run.config["reference"].get("kwargs", {})
    states = {k: v.data for k, v in model.get_states().items()}
    _, longest, new = traffic.limits(run.workload)
    sides = ("served",) if fault else ("served", "control")
    out = {name: [True, 0.0] for name in sides}
    for ids, full in zip(prompts, fulls):
        seqs = np.zeros((1, longest + new), np.int32)
        seqs[0, :len(full)] = full
        for name in sides:
            tokens = (None if name == "served" else ref.lower_precision_choice(
                states, seqs, chk["control"]["lower"], **kwargs))
            shortfall, _ = ref.served_shortfall(states, seqs, tokens=tokens,
                                                **kwargs)
            ok, worst = compare.served_within_margin(
                np.asarray(shortfall), [len(ids)], [len(full)],
                chk["margin"])
            out[name] = [out[name][0] and ok, max(out[name][1], worst)]
    result = {"margin": chk["margin"], "lower": chk["control"]["lower"],
              "streams": len(fulls),
              "served_correct": out["served"][0],
              "served_worst": out["served"][1]}
    if not fault:
        result.update(control_correct=out["control"][0],
                      control_worst=out["control"][1])
    if not fault or result["served_correct"]:
        by_path = agreement(run, model, prompts, fulls)
        result["selection_disagreement"] = float(np.mean(by_path["prefill"]))
        for path, by_layer in by_path.items():
            result[f"selection_disagreement_{path}_by_layer"] = by_layer
        result["selection_disagreement_decode"] = float(
            np.mean(by_path["decode"]))
    if fault:
        result["fault"] = fault
    return result


def main(argv=None):
    import jax

    from singa_tpu import device

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--witness", choices=("float32", "bfloat16"))
    args = ap.parse_args(argv)
    cell, config, workload = cell_mod.load_cell(args.workload)
    device.use_compile_cache()     # the cell's programs, as run.py keeps them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    the_run = cell_mod.Run(cell=cell, config=config, workload=workload,
                           seconds=0.0, trace=False, seed=args.seed,
                           t_process_start=time.perf_counter())
    if args.witness:
        print(json.dumps(witness(the_run, args.witness)), flush=True)
        return 0
    result = run(the_run, fault=args.fault)
    print(json.dumps(result), flush=True)
    if args.fault:
        # where the tokens cannot tell it, `selection_disagreement` is
        # the reading that does
        return 0 if not result["served_correct"] else 1
    return 0 if result["served_correct"] and not result["control_correct"] \
        else 1


if __name__ == "__main__":
    sys.exit(main())
