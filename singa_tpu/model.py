"""Model: the user-facing training class.

Reference parity: `python/singa/model.py` — `Model(Layer)` with
`compile(inputs, is_train, use_graph, sequential)`, user-overridden
`forward` and `train_one_batch`, `train()/eval()` flags,
`save_states/load_states` (zip of npz + aux meta), `set_optimizer`.

TPU-native graph mode: the reference's `compile(use_graph=True)` runs
one traced forward/backward with `Device::EnableGraph(true)`, then
replays `Graph::Run()` each step (SURVEY.md §1). Here the same
user-level contract lowers to ONE `jax.jit`-compiled XLA program per
step: `compile` traces `train_one_batch` with params / layer states /
optimizer state / RNG key bound to jit tracers, captures their updated
values as program outputs, and replays the compiled executable each
call with buffer donation (XLA aliases param memory — the reference's
in-place Block mutation, done the immutable way).

Eager mode (`use_graph=False`) runs the identical Python code per-op —
the graph-vs-eager loss parity test is the key invariant kept from the
reference (`test/python/test_model.py`).
"""
from __future__ import annotations

import io
import json
import os
import time
import zipfile
from collections import OrderedDict
from contextlib import contextmanager
from typing import Dict, List, Optional

import jax
import numpy as np

from . import autograd, hlo_profile, stats as stats_mod, \
    tensor as tensor_mod, trace as trace_mod
from .layer import Layer
from .tensor import Tensor


class Model(Layer):
    """Reference: `model.Model`."""

    def __init__(self, name=None):
        super().__init__(name)
        self._optimizer = None
        self._jit_step = None
        self._jit_fwd = None
        self._use_graph = False
        self._mesh = self._rules = self._batch_specs = None
        self._plan = None
        # Per-model gradient-accumulation override (None = defer to
        # the process knob, device.set_grad_accum / stats config).
        self._grad_accum = None
        self.training = True

    # -- configuration -----------------------------------------------------
    def set_optimizer(self, optimizer):
        self._optimizer = optimizer

    @property
    def optimizer(self):
        return self._optimizer

    def compile(self, inputs: List[Tensor], is_train: bool = True,
                use_graph: bool = False, sequential: bool = False,
                mesh=None, rules=None, batch_specs=None,
                grad_accum=None, plan=None):
        """Reference: `Model.compile` — one tracing pass to initialize
        params (lazy shape inference), then optionally arm graph mode.

        `sequential` is accepted for API parity (the reference uses it
        to serialize graph exec; XLA owns scheduling here).

        Mesh mode (TPU-native, no reference equivalent): passing a
        `jax.sharding.Mesh` turns the compiled step into one SPMD
        program over the mesh — params laid out by `rules`
        (`parallel.ShardingRules`), batch dims sharded over the "data"
        axis (`batch_specs` overrides per-input), gradients reduced by
        XLA over ICI. This subsumes DistOpt: same math, one program.

        `grad_accum=n` arms microbatched gradient accumulation for
        this model (overriding the process knob
        `device.set_grad_accum`): the train step splits its batch into
        n microbatches, scans forward/backward over them inside the
        compiled program (eager mode loops the same microbatches with
        one fused optimizer dispatch), accumulates gradients in fp32,
        and applies the optimizer once on the mean. Batch sizes must
        divide by n. `grad_accum=1` pins accumulation OFF regardless
        of the process knob; None defers to it.

        `plan` (a `parallel.ParallelPlan`, ISSUE 10) is the multi-axis
        spelling of mesh mode: it names the mesh geometry
        (dp x model x pipe x expert x seq), the sharding rules, and
        the pipeline/MoE policy in one object. compile builds the
        mesh from it, wires it into every mesh-aware layer
        (`PipelineStack`, `MoE`, `MultiHeadAttention` — anything with
        a `mesh` attribute left at None), and keys the AOT export
        cache on `plan.fingerprint()`. When neither `plan` nor `mesh`
        is given, the process default (`device.set_parallel_plan`)
        applies.
        """
        if plan is None and mesh is None:
            from .parallel import plan as plan_mod

            plan = plan_mod.process_plan()
        if plan is not None:
            if mesh is not None:
                raise ValueError(
                    "compile: pass either plan= or mesh=, not both "
                    "(the plan builds its own mesh)")
            mesh = plan.build_mesh()
            if rules is None:
                rules = plan.build_rules()
            # wire the mesh + plan policy into every mesh-aware layer;
            # a RE-compile with a different plan re-wires everything
            # the previous plan set (layers track which attrs the
            # user pinned vs the plan filled)
            stack = [self]
            while stack:
                l = stack.pop()
                if l is not self:
                    if hasattr(l, "_apply_plan"):
                        l._apply_plan(plan, mesh)
                    elif hasattr(l, "mesh") and (
                            l.mesh is None
                            or getattr(l, "_mesh_from_plan", False)):
                        l.mesh = mesh
                        l._mesh_from_plan = True
                stack.extend(l.sublayers.values())
        self._plan = plan
        if grad_accum is not None:
            grad_accum = int(grad_accum)
            if grad_accum < 1:
                raise ValueError(
                    f"grad_accum must be >= 1, got {grad_accum}")
        self._grad_accum = grad_accum
        self.train(is_train)
        dev = inputs[0].device if inputs else None
        if dev is not None:
            dev.EnableGraph(use_graph)
        # One forward initializes all lazy params.  The default path
        # runs it under `jax.eval_shape` at batch 1: network ops trace
        # abstractly (zero XLA compilation), while param fills compute
        # host-side numpy values from the concrete RNG key — so
        # ResNet-50 compile is ~2 s where the round-4 jitted-init
        # design paid a 17 s XLA backend compile of the init program.
        # Falls back to the eager per-op init if the trace fails or a
        # custom initialize() depends on concrete input values.
        if inputs and not self.param_tensors():
            # Initialization runs in EVAL mode: param creation must not
            # depend on input values or advance training state (BN
            # running stats stay at their init values, no dropout keys
            # are consumed).  The reference's compile pass runs with
            # placeholder data, so its BN stats absorb garbage; here
            # compile is a pure shape+RNG pass — which is also what
            # lets `_eval_shape_init_forward` skip XLA entirely.
            self.train(False)
            try:
                if not self._eval_shape_init_forward(inputs, dev):
                    self._host_init_forward(inputs, dev)
            finally:
                self.train(is_train)
        elif inputs:
            # Params already exist (a forward ran before compile):
            # run the tracing forward in place.
            self.forward(*inputs)
        self._use_graph = use_graph or mesh is not None
        self._mesh, self._rules, self._batch_specs = mesh, rules, batch_specs
        self._jit_step = None  # (re)built lazily on first train_one_batch
        self._jit_fwd = None
        if dev is not None:
            dev.EnableGraph(False)

    def _eval_shape_init_forward(self, inputs, dev) -> bool:
        """Run the lazy-param-init forward under `jax.eval_shape` —
        the zero-compile init path (VERDICT r4 next #6).

        The network ops trace abstractly (no XLA compilation, no
        execution — the 17+ s backend compile of the batch-1 init
        program for ResNet-50 disappears), while the `initialize`
        hooks draw from the CONCRETE host RNG key, so param values
        are computed eagerly as tiny per-shape programs and match the
        eager init path bit-for-bit.  Requires init to be
        value-independent, which eval-mode init guarantees for the
        in-tree layers; models whose eval forward rebinds state from
        input-dependent values leak a tracer into a param/state — we
        detect that and fall back (returning False leaves the model
        untouched)."""
        from .device import get_default_device

        cpu = get_default_device()
        full = os.environ.get("SINGA_TPU_INIT_FULL_BATCH", "0") == "1"
        specs = []
        for t in inputs:
            shape = tuple(t.shape)
            if not full and len(shape) >= 1 and shape[0] > 1:
                shape = (1,) + shape[1:]
            specs.append(jax.ShapeDtypeStruct(shape, t.dtype))
        borrow = dev is not None and dev is not cpu
        saved_cpu_key = cpu._rng_key
        if borrow:
            cpu._rng_key = jax.device_put(np.asarray(dev._rng_key),
                                          cpu.jax_device)
        snap = _lazy_snapshot(self)

        def init_fn(*batch):
            xs = [tensor_mod.from_raw(b, cpu) for b in batch]
            self.forward(*xs)
            return 0

        def _undo():
            _lazy_restore(self, snap)
            cpu._rng_key = saved_cpu_key

        try:
            jax.eval_shape(init_fn, *specs)
        except Exception as e:
            import sys

            print(f"singa_tpu: eval_shape init failed "
                  f"({type(e).__name__}: {e}); falling back",
                  file=sys.stderr)
            _undo()
            return False
        leaked = [t for t in self.param_tensors() + self.state_tensors()
                  if isinstance(t.data, jax.core.Tracer)]
        if leaked:
            import sys

            print("singa_tpu: eval_shape init leaked tracers into "
                  f"{len(leaked)} tensors (value-dependent init); "
                  "falling back", file=sys.stderr)
            _undo()
            return False
        if borrow:
            dev._rng_key = jax.device_put(np.asarray(cpu._rng_key),
                                          dev.jax_device)
            cpu._rng_key = saved_cpu_key
        if dev is not None and dev is not cpu:
            for t in self.param_tensors() + self.state_tensors():
                t.to_device(dev)
        return True

    def _host_init_forward(self, inputs, dev):
        """Run the param-init forward on host CPU, borrowing `dev`'s RNG
        stream so `dev.SetRandSeed(...)` still governs init values, then
        move every created param/state onto `dev`.

        Multi-controller inputs (global arrays spanning processes) are
        replaced by their local shard for this pass — lazy init only
        reads feature dims, which batch shardings leave whole.

        Uses the same batch-1 slicing policy as
        `_eval_shape_init_forward`, and compile() wraps both paths in
        eval mode, so
        the two init paths leave identical model state (params by RNG
        determinism; BN running stats stay at creation values — eval
        mode never updates them).
        """
        from .device import get_default_device

        cpu = get_default_device()
        full = os.environ.get("SINGA_TPU_INIT_FULL_BATCH", "0") == "1"
        borrow = dev is not None and dev is not cpu
        if borrow:
            saved_cpu_key = cpu._rng_key
            cpu._rng_key = jax.device_put(dev._rng_key, cpu.jax_device)
        try:
            host_inputs = []
            for t in inputs:
                arr = t.data
                if not getattr(arr, "is_fully_addressable", True):
                    arr = arr.addressable_shards[0].data
                arr = np.asarray(arr)
                if not full and arr.ndim >= 1 and arr.shape[0] > 1:
                    arr = arr[:1]
                h = t.clone()
                h.data = jax.device_put(arr, cpu.jax_device)
                h.device = cpu
                host_inputs.append(h)
            self.forward(*host_inputs)
        finally:
            if borrow:
                dev._rng_key = jax.device_put(cpu._rng_key, dev.jax_device)
                cpu._rng_key = saved_cpu_key
        if dev is not None and dev is not cpu:
            for t in self.param_tensors() + self.state_tensors():
                t.to_device(dev)

    def train(self, mode: bool = True):
        self.training = mode
        autograd.training = mode

    def eval(self):
        self.train(False)

    # -- user-overridable --------------------------------------------------
    def forward(self, *xs):
        raise NotImplementedError

    def loss(self, out, ty):
        """Default loss hook; user models commonly override
        train_one_batch wholesale (reference examples do)."""
        return autograd.softmax_cross_entropy(out, ty)

    def optim(self, loss):
        return self._optimizer.backward_and_update(loss)

    def train_one_batch(self, x: Tensor, y: Tensor):
        if self._optimizer is None:
            raise RuntimeError(
                "train_one_batch requires an optimizer: call "
                "model.set_optimizer(...) before training"
            )
        out = self.forward(x)
        l = self.loss(out, y)
        self.optim(l)
        # Step accounting for cache observability: retraces/step after
        # warmup is the retrace-storm signal (stats.cache_stats()).
        # Counted here (not in __call__) so user models overriding
        # train_one_batch wholesale — the reference's idiom — opt out
        # explicitly rather than silently, and the graph path counts
        # in _JitStep.__call__ where a trace is one step too.
        stats_mod.count_train_step()
        return out, l

    def __call__(self, *args, **kwargs):
        """Reference: `Model.__call__` routes to `train_one_batch` in
        train mode (graph replay when compiled with use_graph) and to
        `forward` in eval mode."""
        if self.training and (self._optimizer is not None or len(args) > 1):
            return self.train_one_batch_dispatch(*args, **kwargs)
        if self._use_graph and not kwargs:
            return self.forward_graph(*args)
        return self.forward(*args, **kwargs)

    # -- graph (jit) execution --------------------------------------------
    def train_one_batch_graph(self, *batch: Tensor):
        """Run `train_one_batch` as one compiled XLA program.

        Called automatically by `train_one_batch_dispatch`; also public
        for direct use. First call traces+compiles; subsequent calls
        replay with donated buffers.
        """
        if self._jit_step is None:
            if getattr(self, "_mesh", None) is not None:
                from .parallel.trainer import ShardedJitStep

                self._jit_step = ShardedJitStep(
                    self, self._mesh, rules=self._rules,
                    batch_specs=self._batch_specs,
                    plan=getattr(self, "_plan", None))
            else:
                self._jit_step = _JitStep(self)
        return self._jit_step(*batch)

    def train_one_batch_dispatch(self, *batch: Tensor):
        if self._use_graph:
            return self.train_one_batch_graph(*batch)
        n = self._accum_n()
        # Spanned HERE (not in train_one_batch) so user models that
        # override train_one_batch wholesale — the reference idiom —
        # still get the eager step on the timeline; the graph path
        # gets its dispatch/device_sync spans in _JitStep instead.
        with trace_mod.span("train_one_batch"):
            if n > 1 and self._optimizer is not None:
                return self._train_one_batch_accum_eager(n, *batch)
            return self.train_one_batch(*batch)

    def _accum_n(self) -> int:
        """Effective gradient-accumulation factor: the per-model
        `compile(grad_accum=...)` override, else the process knob
        (`device.set_grad_accum`)."""
        if self._grad_accum is not None:
            return self._grad_accum
        return stats_mod.grad_accum_n()

    def _train_one_batch_accum_eager(self, n: int, *batch: Tensor):
        """Eager-mode gradient accumulation: split the batch into n
        microbatches (`data.microbatches`), run the user's
        `train_one_batch` per microbatch with the optimizer in capture
        mode (backward runs — scaled seed included — but the apply is
        deferred), accumulate gradients in fp32 with a jitted adder,
        and apply the optimizer ONCE on the mean via
        `opt.apply_accumulated` — so an n-accum eager step pays one
        fused optimizer dispatch instead of n, and the StepGuard /
        DynamicLossScaler / bf16-slot policies all act once on the
        accumulated gradients, exactly like the scan-fused graph step.

        Returns the same pytree shape `train_one_batch` returns:
        batch-dim outputs are the microbatch outputs concatenated
        back to the full batch; scalar (loss) leaves become the mean
        over microbatches."""
        import jax.numpy as jnp

        from . import data as data_mod

        opt = self._optimizer
        micro = data_mod.microbatches(list(batch), n)
        order = None
        acc = loss_sum = None
        outs = []
        for mb in micro:
            opt._accum_begin()
            try:
                out = self.train_one_batch(*mb)
            finally:
                cap = opt._accum_end()
            if len(cap) != 1:
                raise RuntimeError(
                    "gradient accumulation requires train_one_batch "
                    "to call backward_and_update exactly once per "
                    f"microbatch; it ran {len(cap)} times")
            loss_t, pairs = cap[0]
            gs = [g.data if isinstance(g, Tensor) else g
                  for _, g in pairs]
            loss_arr = (loss_t.data if isinstance(loss_t, Tensor)
                        else jnp.asarray(loss_t))
            if order is None:
                order = [p for p, _ in pairs]
                acc, loss_sum = _accum_seed(gs, loss_arr)
            else:
                if [id(p) for p, _ in pairs] != [id(p) for p in order]:
                    raise RuntimeError(
                        "gradient accumulation: the (param, grad) "
                        "pair order changed across microbatches — "
                        "train_one_batch must be structurally "
                        "identical per microbatch")
                acc, loss_sum = _accum_add(acc, gs, loss_sum, loss_arr)
            outs.append(_unwrap_out(out))
        mb_size = micro[0][0].data.shape[0] if hasattr(
            micro[0][0], "data") else len(micro[0][0])
        stats_mod.note_accum_build(n, mb_size, mb_size * n)
        opt.apply_accumulated(loss_sum, list(zip(order, acc)), n)
        stacked = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *outs)
        merged = _merge_accum_out(stacked, mb_size)
        dev = batch[0].device if batch and isinstance(
            batch[0], Tensor) else None
        return jax.tree_util.tree_map(
            lambda a: tensor_mod.from_raw(a, dev), merged)

    def _class_source_digest(self, h) -> None:
        """Fold this model's class identity + source into hasher `h` —
        the shared prelude of every topology fingerprint (a forward()
        edit must orphan cached AOT artifacts)."""
        import inspect

        h.update(type(self).__qualname__.encode())
        try:
            h.update(inspect.getsource(type(self)).encode())
        except (OSError, TypeError):
            pass  # source unavailable (REPL/frozen): inventory only

    # Layer machinery + per-run mutables that must NOT key an AOT
    # artifact: tensors/sublayers are inventoried separately, and
    # train/eval flags ride the export key's own extras.
    _FP_SKIP_ATTRS = frozenset({
        "_params", "_sublayers", "_state_attrs", "_initialized",
        "training", "_use_graph", "_jit_step", "_jit_fwd",
        "_optimizer", "_mesh", "_rules", "_batch_specs", "_plan",
    })

    def topology_fingerprint(self) -> str:
        """Stable identity of this model's traced program structure:
        class + source + the full param/state inventory (names,
        shapes, dtypes) + every layer's scalar CONFIG attributes —
        two instances with identical weights but e.g. `causal=True`
        vs `False`, or a stride that leaves kernel shapes unchanged,
        trace different programs and must never share an artifact.
        Keys the `export_cache` artifact store; models whose program
        is data-driven rather than source-driven override this
        (`sonnx.SONNXModel` hashes the imported ONNX graph)."""
        import hashlib
        import json

        h = hashlib.sha256()
        self._class_source_digest(h)
        for name, t in sorted(self.get_params().items()) + sorted(
                self.get_states().items()):
            h.update(f"{name}:{tuple(t.shape)}:{t.dtype}".encode())

        def config_of(layer):
            out = {}
            for k, v in layer.__dict__.items():
                if k in Model._FP_SKIP_ATTRS:
                    continue
                if isinstance(v, (bool, int, float, str, type(None))):
                    out[k] = v
                elif isinstance(v, (tuple, list)) and all(
                        isinstance(x, (bool, int, float, str,
                                       type(None))) for x in v):
                    out[k] = list(v)
            return out

        stack = [("", self)]
        while stack:
            path, l = stack.pop()
            h.update(json.dumps([path, config_of(l)],
                                sort_keys=True).encode())
            for k in sorted(l.sublayers):
                stack.append((f"{path}/{k}", l.sublayers[k]))
        return h.hexdigest()

    def cache_stats(self):
        """Snapshot of every executable-cache's counters
        (`singa_tpu.stats.cache_stats()`): the DAG backward cache, the
        per-op executable cache, and the fused-optimizer cache, plus
        the global train-step count. The numbers are process-global
        (caches are shared across models by design — two models with
        identical DAG structure share executables)."""
        return stats_mod.cache_stats()

    def step_hlo_text(self, *batch, optimized: bool = True) -> str:
        """HLO of the whole-step jit program for `batch` (never
        executed — model/optimizer arrays are untouched apart from
        `_ensure_opt_slots` pre-creating missing slot zeros). The
        input to `hlo_profile.bytes_accessed`/`profile_hlo`: how tests
        and tools measure a byte-diet knob's effect without a chip.
        `optimized=False` returns the pre-optimization HLO instead
        (no XLA compile paid) — the view where the remat policy's
        checkpoint barriers survive, which is what
        `hlo_profile.peak_bytes_estimate` meters (see
        `_JitStep.lowered_text`). Reuses (or primes) the model's own
        `_jit_step` executable, so inspecting a training model — or
        inspecting then training — pays the whole-step XLA compile
        once, not twice."""
        if self._jit_step is None:
            if getattr(self, "_mesh", None) is not None:
                from .parallel.trainer import ShardedJitStep

                self._jit_step = ShardedJitStep(
                    self, self._mesh, rules=self._rules,
                    batch_specs=self._batch_specs,
                    plan=getattr(self, "_plan", None))
            else:
                self._jit_step = _JitStep(self)
        return self._jit_step.lowered_text(*batch, optimized=optimized)

    def _ensure_forward_exec(self) -> "_JitForward":
        """The model's forward-executable wrapper, created lazily —
        shared by `forward_graph`, the serving engine (`serve.py`
        dispatches through it so requests hit the same warm AOT
        artifacts), and the prewarm tool's dry-run key probe."""
        if self._jit_fwd is None:
            self._jit_fwd = _JitForward(self)
        return self._jit_fwd

    def forward_graph(self, *xs: Tensor):
        """Run `forward` as one compiled XLA program (the eval-path
        analogue of `train_one_batch_graph`; reference eval replays the
        same buffered Graph)."""
        return self._ensure_forward_exec()(*xs)

    # -- checkpoint --------------------------------------------------------
    def state_snapshot(self, aux_states: Optional[Dict] = None):
        """Capture a consistent (states, meta) snapshot of the model +
        optimizer. The returned arrays are the CURRENT device buffers
        by reference. NOTE: a graph-mode train step DONATES these
        buffers to XLA (`_JitStep`, donate_argnums) — deferred readers
        must fork them first (`checkpoint.AsyncCheckpointer` makes
        device-side copies); immediate serialization (`save_states`)
        is safe as-is."""
        model_states = self.get_states()
        states = {k: v.data for k, v in model_states.items()}
        opt_meta = {}
        if self._optimizer is not None:
            opt_meta["step_counter"] = int(self._optimizer.step_counter)
            # Optimizer slots are keyed by id(param) in-memory; persist
            # them by param NAME so they survive into a fresh process.
            name_of = {id(t): n for n, t in model_states.items()}
            for pid, slots in self._optimizer.states.items():
                pname = name_of.get(pid)
                if pname is None:
                    continue
                for slot, arr in slots.items():
                    states[f"__opt__/{pname}/{slot}"] = arr
        from . import resilience

        if resilience.guard_active():
            # scale/backoff history resumes with the weights — a
            # restart must not restart the loss scale from init
            opt_meta["resilience"] = resilience.export_host_state()
        meta = {"aux": _jsonable(aux_states or {}), "opt": opt_meta,
                "names": list(states.keys())}
        return states, meta

    @staticmethod
    def write_states_zip(fpath: str, states: Dict, meta: Dict):
        """Serialize a `state_snapshot` to the checkpoint zip format
        (device→host transfer happens here, per array)."""
        with zipfile.ZipFile(fpath, "w") as zf:
            for name, arr in states.items():
                buf = io.BytesIO()
                arr = np.asarray(arr)
                if arr.dtype.name == "bfloat16":
                    # np.save round-trips ml_dtypes bf16 as raw V2
                    # void (dtype lost); store the exact values as
                    # fp32 (bf16 ⊂ fp32) — the slot_dtype policy
                    # re-quantizes on the first post-restore update.
                    arr = arr.astype(np.float32)
                np.save(buf, arr)
                zf.writestr(name.replace("/", "__SLASH__") + ".npy",
                            buf.getvalue())
            zf.writestr("__meta__.json", json.dumps(meta))

    def save_states(self, fpath: str, aux_states: Optional[Dict] = None):
        """Reference: `Model.save_states` — zipfile of per-tensor npz
        plus a json meta blob with aux states. Synchronous; see
        `singa_tpu.checkpoint.AsyncCheckpointer` for the non-blocking
        variant."""
        states, meta = self.state_snapshot(aux_states)
        self.write_states_zip(fpath, states, meta)

    def load_states(self, fpath: str) -> Dict:
        """Reference: `Model.load_states`. Returns aux states dict."""
        with zipfile.ZipFile(fpath, "r") as zf:
            meta = json.loads(zf.read("__meta__.json"))
            arrays = {}
            for name in meta["names"]:
                raw = zf.read(name.replace("/", "__SLASH__") + ".npy")
                arrays[name] = np.load(io.BytesIO(raw))
        model_states = {k: v for k, v in arrays.items()
                        if not k.startswith("__opt__/")}
        self.set_states(model_states)
        if self._optimizer is not None and meta.get("opt"):
            import jax.numpy as jnp

            self._optimizer.step_counter = meta["opt"].get("step_counter", 0)
            tensor_of = self.get_states()
            for key, arr in arrays.items():
                if not key.startswith("__opt__/"):
                    continue
                _, pname, slot = key.split("/", 2)
                t = tensor_of.get(pname)
                if t is not None:
                    self._optimizer.states.setdefault(id(t), {})[slot] = jnp.asarray(arr)
        if meta.get("opt", {}).get("resilience"):
            from . import resilience

            resilience.import_host_state(meta["opt"]["resilience"])
        self._jit_step = None  # state changed: force retrace
        self._jit_fwd = None
        return meta.get("aux", {})

    def fit_resumable(self, manager, batch_fn, total_steps: int,
                      save_every: int = 10, metrics=None):
        """Crash-consistent training loop: restore the latest VALID
        checkpoint from `manager` (a `checkpoint.CheckpointManager` —
        corrupt/truncated newest checkpoints are skipped via their
        content-digest manifests), then train to `total_steps`,
        checkpointing every `save_every` steps. `batch_fn(step)` must
        deterministically produce that step's (x, y) batch so a
        resumed run's loss trajectory matches the uninterrupted one.
        `metrics` (a `trace.MetricsLogger`) logs one structured JSONL
        record per executed step. Returns {step: loss} for the steps
        this call ran. See `singa_tpu.resilience.run_resumable`."""
        from . import resilience

        return resilience.run_resumable(self, manager, batch_fn,
                                        total_steps,
                                        save_every=save_every,
                                        metrics=metrics)


def _lazy_snapshot(root: Layer):
    """Record every layer's lazy-init state (for rollback if a traced
    init forward fails midway, leaving tracer-valued params behind)."""
    recs = []
    stack = [root]
    while stack:
        l = stack.pop()
        recs.append((l, l._initialized,
                     OrderedDict(l.__dict__.get("_params", ())),
                     list(l.__dict__.get("_state_attrs", ())),
                     set(l.sublayers.keys())))
        stack.extend(l.sublayers.values())
    return recs


def _lazy_restore(root: Layer, recs):
    for l, inited, params, state_attrs, subkeys in recs:
        l._initialized = inited
        l.__dict__["_params"] = OrderedDict(params)
        l.__dict__["_state_attrs"] = list(state_attrs)
        subs = l.__dict__.get("_sublayers")
        if subs is not None:
            for k in [k for k in subs if k not in subkeys]:
                del subs[k]


def _jsonable(d):
    out = {}
    for k, v in d.items():
        if isinstance(v, (int, float, str, bool, list, dict, type(None))):
            out[k] = v
        else:
            out[k] = float(v) if np.isscalar(v) else np.asarray(v).tolist()
    return out


@contextmanager
def _bound_model(params, states, dev, pvals, svals, key):
    """Bind tracer/program values onto the live param/state tensors and
    the device RNG key for the duration of a traced call, restoring the
    concrete arrays afterwards. The shared functionalization core of
    `_JitStep` and `_JitForward`."""
    saved_p = [p.data for p in params]
    saved_s = [s.data for s in states]
    saved_key = dev._rng_key
    try:
        for p, v in zip(params, pvals):
            p.data = v
        for s, v in zip(states, svals):
            s.data = v
        dev._rng_key = key
        yield
    finally:
        for p, v in zip(params, saved_p):
            p.data = v
        for s, v in zip(states, saved_s):
            s.data = v
        dev._rng_key = saved_key


def _unwrap_out(out):
    return jax.tree_util.tree_map(
        lambda t: t.data if isinstance(t, Tensor) else t,
        out,
        is_leaf=lambda t: isinstance(t, Tensor),
    )


# ---------------------------------------------------------------------------
# Gradient-accumulation helpers (ISSUE 4). The fp32 accumulator math is
# deliberately identical between the eager loop (jitted seed/add below)
# and the scan-fused graph step (same expressions traced into the scan
# body), so the two modes accumulate bit-identically: the sum order is
# sequential-by-microbatch in both, and the final mean is an
# elementwise division (never reassociated by fusion).
# ---------------------------------------------------------------------------
def _accum_seed_fn(gs, loss):
    import jax.numpy as jnp

    return ([g.astype(jnp.float32) for g in gs],
            jnp.mean(jnp.asarray(loss)).astype(jnp.float32))


def _accum_add_fn(acc, gs, loss_sum, loss):
    import jax.numpy as jnp

    return ([a + g.astype(jnp.float32) for a, g in zip(acc, gs)],
            loss_sum + jnp.mean(jnp.asarray(loss)).astype(jnp.float32))


# One jitted executable each, cached by jax per grad-list structure;
# the running accumulator and loss sum are donated so XLA adds in
# place instead of round-tripping fresh buffers every microbatch.
_accum_seed = jax.jit(_accum_seed_fn)
_accum_add = jax.jit(_accum_add_fn, donate_argnums=(0, 2))


def _merge_accum_out(stacked, mb: int):
    """Collapse per-microbatch outputs stacked on a leading [n] axis
    back to the monolithic step's output shape: leaves carrying the
    microbatch dim are concatenated to the full batch ([n, mb, ...] →
    [n*mb, ...]), inexact leaves without it (the loss scalar) become
    the mean over microbatches, and anything else (integer metadata)
    keeps the last microbatch's value.

    Known limitation: batch-ness is inferred by SHAPE (leading dim ==
    microbatch size). A non-batch output vector whose length happens
    to equal the microbatch size is indistinguishable from a
    per-sample output and gets concatenated rather than averaged —
    pick a microbatch size that differs from such output dims (this
    is inherent to shape-based inference; train_one_batch outputs
    carry no axis annotations)."""
    import jax.numpy as jnp

    def leaf(a):
        a = jnp.asarray(a)
        if a.ndim >= 2 and a.shape[1] == mb:
            return a.reshape((a.shape[0] * mb,) + a.shape[2:])
        if jnp.issubdtype(a.dtype, jnp.inexact):
            return jnp.mean(a, axis=0)
        return a[-1]

    return jax.tree_util.tree_map(leaf, stacked)


def _checkpoint_policy(policy):
    """Resolve a validated remat-policy config value
    (`stats.remat_policy()`) to the jax.checkpoint policy callable.
    None stays None (checkpoint's own default = nothing saveable —
    but a None CONFIG means remat is OFF and no checkpoint wraps at
    all; callers branch on the config before resolving)."""
    from jax import checkpoint_policies as _cp

    if policy is None:
        return None
    if isinstance(policy, str):
        return getattr(_cp, policy)
    name, keep = policy  # ("save_anything_but_these_names", names)
    return _cp.save_anything_except_these_names(*keep)


class _JitForward:
    """Compiles `model.forward` into one XLA program (inference path).

    Same functionalization trick as `_JitStep` (via `_bound_model`),
    minus optimizer state and buffer donation (params are read-only
    here). The device RNG key is threaded through so eval-time
    stochastic ops stay reproducible. Layer-state updates made during a
    training-mode forward (BN running stats) are captured as program
    outputs and written back.

    Compiled executables are cached per (training-flag, non-Tensor
    args): the train/eval flag changes the traced program (dropout on /
    off), and plain-Python positional args are baked in as statics, not
    traced.

    Mesh mode: when the model was compiled over a mesh, inputs are laid
    out to match — params by the model's `ShardingRules`, states/key
    replicated, batch dims sharded — so the sharded train path and this
    eval path never mix incompatible device commitments.
    """

    def __init__(self, model: "Model"):
        self.model = model
        self.params: List[Tensor] = model.param_tensors()
        self.states: List[Tensor] = model.state_tensors()
        self._compiled: Dict = {}

    def _device(self):
        if self.params:
            return self.params[0].device
        from .device import get_default_device

        return get_default_device()

    def _build(self, tensor_pos, statics, nargs):
        model, params, states = self.model, self.params, self.states

        def fwd_fn(pvals, svals, key, batch):
            # int8 forward (ISSUE 19): quantized param leaves ride
            # the stream as (payload int8, scale f32) pairs —
            # dequantized once at program entry (fp32 accumulation
            # downstream). tuple-ness is the dispatch; the pytree
            # structure change retraces/orphans fp32 programs.
            pvals = [p[0].astype(p[1].dtype) * p[1]
                     if isinstance(p, tuple) else p for p in pvals]
            dev = self._device()
            with _bound_model(params, states, dev, pvals, svals, key):
                args = [None] * nargs
                for i, b in zip(tensor_pos, batch):
                    args[i] = tensor_mod.from_raw(b, dev)
                it = iter(statics)
                for i in range(nargs):
                    if args[i] is None:
                        args[i] = next(it)
                with autograd.layer_scope(model.name):
                    out_arrays = _unwrap_out(model.forward(*args))
                new_s = [s.data for s in states]
                return out_arrays, new_s, dev._rng_key

        return jax.jit(fwd_fn)

    def _quant_pvals(self, pvals):
        """Swap eligible param leaves for (payload, scale) pairs when
        int8 inference is armed (eval mode, single device). Host-side
        quantization is memoized per param buffer identity — a
        training step swaps the buffer and invalidates the entry.
        Small leaves (LN gammas, biases) stay fp32: no byte win, real
        precision cost."""
        from . import quant as quant_mod

        if (not quant_mod.enabled() or self.model.training
                or getattr(self.model, "_mesh", None) is not None):
            return pvals
        memo = getattr(self, "_quant_memo", None)
        if memo is None:
            memo = self._quant_memo = {}
        out = []
        for i, p in enumerate(pvals):
            if not quant_mod.forward_eligible(p):
                out.append(p)
                continue
            hit = memo.get(i)
            if hit is None or hit[0] is not p:
                memo[i] = (p, quant_mod.quantize_forward_leaf(p))
                quant_mod.stats_counters()["weights_quantized"] += 1
            out.append(memo[i][1])
        return out

    def _place_inputs(self, pvals, svals, key, batch_arrays):
        """Mesh-mode placement (single-device: identity)."""
        mesh = getattr(self.model, "_mesh", None)
        if mesh is None:
            return pvals, svals, key, batch_arrays
        from jax.sharding import NamedSharding

        from .parallel.sharding import (
            ShardingRules,
            batch_sharding,
            replicated,
        )

        rules = getattr(self.model, "_rules", None) or ShardingRules()
        name_of = {id(t): n for n, t in self.model.get_params().items()}
        pvals = [
            jax.device_put(
                v, rules.sharding_for(mesh, name_of.get(id(p), ""),
                                      p.data.shape))
            for p, v in zip(self.params, pvals)
        ]
        rep = replicated(mesh)
        svals = [jax.device_put(v, rep) for v in svals]
        key = jax.device_put(key, rep)
        specs = getattr(self.model, "_batch_specs", None)
        if specs is not None:
            shs = [NamedSharding(mesh, s) for s in specs]
        else:
            shs = [batch_sharding(mesh, getattr(b, "ndim", 0))
                   for b in batch_arrays]
        batch_arrays = tuple(
            jax.device_put(b, s) for b, s in zip(batch_arrays, shs)
        )
        return pvals, svals, key, batch_arrays

    def _export_identity(self, tensor_pos, statics, args):
        """(key, parts) of the AOT artifact a forward dispatch with
        these program args resolves to — the ONE definition shared by
        the dispatch path (`_obtain`) and the prewarm tool's dry-run
        probe (`export_key`), so the two can never drift."""
        from . import export_cache

        return export_cache.step_key(
            self.model, None, "forward", args,
            extras={"training": self.model.training,
                    "tensor_pos": list(tensor_pos),
                    # address-free: repr() of a plain object embeds
                    # its 0x... address and would make keys
                    # process-unique (never a warm hit)
                    "statics": [export_cache._scalarize(s)
                                for s in statics]})

    def export_key(self, *xs) -> str:
        """Store key of the artifact a `__call__` with these inputs
        would load — computed WITHOUT tracing, dispatching, or
        touching the hit/miss counters. Applies the same bucket
        padding `__call__` would, so feeding real (unbucketed) request
        shapes answers for the bucket they land in. Drives
        `tools/prewarm.py --dry-run` ("which (model, bucket) artifacts
        are missing?")."""
        from . import export_cache

        tensor_pos = tuple(i for i, x in enumerate(xs)
                           if isinstance(x, Tensor))
        statics = tuple(x for x in xs if not isinstance(x, Tensor))
        batch_arrays = tuple(xs[i].data for i in tensor_pos)
        if (export_cache.bucket_policy() is not None and batch_arrays
                and not self.model.training):
            batch_arrays, _ = export_cache.pad_batch_to_bucket(
                batch_arrays)
            batch_arrays = tuple(batch_arrays)
        dev = self._device()
        pvals, svals, key, batch_arrays = self._place_inputs(
            self._quant_pvals([p.data for p in self.params]),
            [s.data for s in self.states],
            dev._rng_key, batch_arrays,
        )
        args = (pvals, svals, key, batch_arrays)
        return self._export_identity(tensor_pos, statics, args)[0]

    def _obtain(self, cache_key, tensor_pos, statics, nargs, args):
        """Forward executable via the AOT store when armed: load the
        serialized artifact (no tracing) or trace once + publish —
        the serving-tier warm start, ONNX-imported models included."""
        from . import export_cache

        if not export_cache.active() or cache_key is None:
            fn = self._build(tensor_pos, statics, nargs)
            export_cache.count_trace(0.0)
            return fn
        key, parts = self._export_identity(tensor_pos, statics, args)
        exp = export_cache.load(key)
        if exp is None:
            built = self._build(tensor_pos, statics, nargs)
            exp = export_cache.export_and_save(key, parts, built, args)
            if exp is None:
                return built
        return jax.jit(exp.call)

    def __call__(self, *xs):
        from . import export_cache

        tensor_pos = tuple(i for i, x in enumerate(xs)
                           if isinstance(x, Tensor))
        statics = tuple(x for x in xs if not isinstance(x, Tensor))
        batch_arrays = tuple(xs[i].data for i in tensor_pos)
        # Pad-to-bucket at dispatch (ISSUE 6): under the pow2 policy a
        # stream of diverse batch/sequence sizes collapses onto at
        # most n_buckets() traced shapes; the padded rows/positions
        # (repeated final sample) are sliced back off the outputs
        # below (export_cache.slice_bucket_out — shape-inferred, the
        # _merge_accum_out caveat applies).
        # Training-mode forwards are NEVER padded: the program writes
        # BN running stats back from new_s, and stats over a padded
        # batch (final sample repeated) are reweighted state
        # corruption — the same contract as train_one_batch
        # ("training batches are not padded implicitly").
        bucket_info = None
        if (export_cache.bucket_policy() is not None and batch_arrays
                and not self.model.training):
            batch_arrays, bucket_info = \
                export_cache.pad_batch_to_bucket(batch_arrays)
            batch_arrays = tuple(batch_arrays)
            if (bucket_info["n_bucket"] == bucket_info["n_real"]
                    and bucket_info["seq_bucket"] ==
                    bucket_info["seq_real"]):
                bucket_info = None  # on bucket edges: nothing to slice
        try:
            from . import quant as _quant_mod

            cache_key = (self.model.training, tensor_pos, statics,
                         _quant_mod.mode())
            if export_cache.active():
                # serialized artifacts are shape-specialized: key the
                # executable cache per abstract batch signature
                cache_key += (tuple(
                    (tuple(int(d) for d in b.shape), str(b.dtype))
                    for b in batch_arrays),)
            fn = self._compiled.get(cache_key)
        except TypeError:  # unhashable static arg: compile fresh
            cache_key, fn = None, None
        dev = self._device()
        pvals, svals, key, batch_arrays = self._place_inputs(
            self._quant_pvals([p.data for p in self.params]),
            [s.data for s in self.states],
            dev._rng_key, batch_arrays,
        )
        if fn is None:
            fn = self._obtain(cache_key, tensor_pos, statics, len(xs),
                              (pvals, svals, key, batch_arrays))
            if cache_key is not None:
                self._compiled[cache_key] = fn
        out, new_s, new_key = fn(pvals, svals, key, batch_arrays)
        if bucket_info is not None:
            out = export_cache.slice_bucket_out(out, bucket_info)
        if self.model.training:
            for s, v in zip(self.states, new_s):
                s.data = v
        # Pin the advanced key back onto the device's own placement so
        # later eager code stays single-device even when params are
        # mesh-sharded (cf. _JitStep._restore_key).
        dev._rng_key = jax.device_put(new_key, dev.jax_device)
        return jax.tree_util.tree_map(
            lambda a: tensor_mod.from_raw(a, dev), out
        )


class _JitStep:
    """Compiles `model.train_one_batch` into a single XLA program.

    The functionalization trick: params, layer states (BN running
    stats), optimizer slots, and the device RNG key are *bound* to jit
    tracers before calling the user's Python `train_one_batch`, and
    their post-step values are collected as program outputs. Outside
    the trace, concrete arrays round-trip through the compiled
    executable with `donate_argnums` so XLA reuses the param HBM —
    the TPU equivalent of the reference scheduler's in-place Block
    update + memory reuse pass (src/core/scheduler/scheduler.cc).
    """

    def __init__(self, model: Model):
        from . import resilience

        self.model = model
        model.get_params()  # names every param: the update's scopes
        self.params: List[Tensor] = model.param_tensors()
        self.states: List[Tensor] = model.state_tensors()
        self.opt = model._optimizer
        self._compiled = None
        self._hlo_rows = None  # graph-profile cache (hlo_profile.py)
        # Export-cache state (ISSUE 6): one executable per abstract
        # batch signature when the AOT store is armed (a serialized
        # artifact is shape-specialized, unlike a polymorphic jit),
        # plus the seen-signature set behind the retrace-storm warning.
        self._by_sig: Dict = {}
        self._batch_sig = None
        self._seen_sigs = set()
        self._from_export = False
        # Gradient-accumulation factor baked into the built executable
        # (1 = off); read from the model/process knob at _build time —
        # toggling requires re-compile(), like donation/step-guard.
        self._accum_built = 1
        # Step-guard state (loss scale + counters) rides the flattened
        # opt-state slot of the jit signature, so the guard's skip /
        # backoff math updates on device with no extra program inputs.
        # Fixed at build time (like donation): toggling the guard
        # requires re-compile().
        self._guard_n = (len(resilience.state_arrays())
                         if resilience.guard_active() else 0)
        # batch signature -> the `jax.stages.Lowered` of the step at
        # that signature (`_note_program`)
        self._lowerings: Dict = {}
        self._noted_sig = None

    # ---- optimizer state flattening -------------------------------------
    def _opt_arrays(self):
        out = [] if self.opt is None else list(self.opt.state_arrays())
        if self._guard_n:
            from . import resilience

            out += resilience.state_arrays()
        return out

    def _bind_opt_arrays(self, arrays):
        arrays = list(arrays)
        if self._guard_n:
            from . import resilience

            resilience.bind_state_arrays(arrays[-self._guard_n:])
            arrays = arrays[:-self._guard_n]
        if self.opt is not None:
            self.opt.set_state_arrays(arrays)

    def _device(self):
        if self.params:
            return self.params[0].device
        from .device import get_default_device

        return get_default_device()

    def _build(self, *batch_arrays, donate=None):
        model, opt = self.model, self.opt
        params, states = self.params, self.states

        def step_fn(pvals, svals, ovals, key, step_counter, batch):
            saved_o = self._opt_arrays()
            dev = self._device()
            saved_step = None if opt is None else opt.step_counter
            with _bound_model(params, states, dev, pvals, svals, key):
                try:
                    self._bind_opt_arrays(ovals)
                    if opt is not None:
                        opt.step_counter = step_counter
                    batch_t = [tensor_mod.from_raw(b, dev) for b in batch]
                    with autograd.layer_scope(model.name):
                        out_arrays = _unwrap_out(
                            model.train_one_batch(*batch_t))
                    new_p = [p.data for p in params]
                    new_s = [s.data for s in states]
                    new_o = self._opt_arrays()
                    new_key = dev._rng_key
                    return out_arrays, new_p, new_s, new_o, new_key
                finally:
                    self._bind_opt_arrays(saved_o)
                    if opt is not None and saved_step is not None:
                        opt.step_counter = saved_step

        # Pre-create optimizer slots so the jit signature (flattened
        # opt state) is stable from step one. step_counter is traced
        # (not static) so LR schedules don't retrigger compilation.
        self._ensure_opt_slots()
        # Gradient accumulation (ISSUE 4): n > 1 swaps the monolithic
        # step body for the scan-fused microbatch accumulator. Baked
        # at build time like donation; requires an optimizer (a
        # no-optimizer step has nothing to accumulate).
        n = self._accum_built = (self.model._accum_n()
                                 if self.opt is not None else 1)
        if n > 1:
            for b in batch_arrays:
                if getattr(b, "ndim", 0) < 1 or b.shape[0] % n:
                    raise ValueError(
                        f"grad_accum={n}: every batch input needs a "
                        f"leading dim divisible by {n}; got shape "
                        f"{getattr(b, 'shape', ())} — see "
                        "singa_tpu.data.microbatches")
            mb = batch_arrays[0].shape[0] // n
            stats_mod.note_accum_build(n, mb,
                                       batch_arrays[0].shape[0])

            def accum_fn(pvals, svals, ovals, key, step_counter,
                         batch):
                return self._accum_step(n, pvals, svals, ovals, key,
                                        step_counter, batch)

            step_fn = accum_fn
        elif (stats_mod.remat_policy() is not None
              and self.opt is not None):
            # Scan-level remat with accumulation OFF (ISSUE 9): the
            # whole batch runs as ONE checkpointed microbatch through
            # the accumulation body (length-1 scan elided inside
            # _accum_scan), so the policy has exactly one definition
            # whether or not grad accumulation is on. Requires the
            # accumulation contract (one backward_and_update per
            # step), which _accum_step validates.
            def remat_fn(pvals, svals, ovals, key, step_counter,
                         batch):
                return self._accum_step(1, pvals, svals, ovals, key,
                                        step_counter, batch)

            step_fn = remat_fn
        # Donation honors the eager-config knob at build time
        # (device.set_buffer_donation); re-compile() to re-arm. The
        # export-cache path forces donation OFF (`donate=False`): a
        # deserialized artifact executes through `Exported.call`,
        # whose caller never donates, and an aliased-input module
        # without donated buffers would silently invalidate arrays the
        # Python side still holds.
        if donate is None:
            donate = stats_mod.donation_enabled()
        donate_argnums = (0, 1, 2, 3) if donate else ()
        return jax.jit(step_fn, donate_argnums=donate_argnums,
                       **self._jit_kwargs(batch_arrays))

    def _jit_kwargs(self, batch_arrays):
        """Hook for sharded subclasses (parallel.trainer.ShardedJitStep)
        to add in/out shardings over a mesh."""
        return {}

    # ---- gradient accumulation (ISSUE 4) ---------------------------------
    def _microbatch_stack(self, n, batch):
        """Reshape every batch array [B, ...] → [n, B/n, ...] (the
        scan axis first). Divisibility is validated at _build;
        re-validated here because jit retraces on new shapes."""
        out = []
        for b in batch:
            if getattr(b, "ndim", 0) < 1 or b.shape[0] % n:
                raise ValueError(
                    f"grad_accum={n}: batch shape "
                    f"{getattr(b, 'shape', ())} has no leading dim "
                    f"divisible by {n}")
            out.append(b.reshape((n, b.shape[0] // n)
                                 + tuple(b.shape[1:])))
        return self._place_microbatches(out)

    def _place_microbatches(self, micro):
        """Hook: sharded subclasses constrain the microbatch layout
        ([n] replicated, batch dims sharded); identity on one
        device."""
        return micro

    def _run_accum_microbatch(self, dev, svals_c, key_c, mb,
                              skip_backward: bool = False):
        """One microbatch forward+backward with the optimizer in
        capture mode: binds states/key, runs the user's
        train_one_batch, and returns (out_arrays, loss_array, pairs,
        new_state_arrays, new_key). The shared body of the discovery
        pass, the scan body, and the sharded local step.

        `skip_backward=True` (the scan-level remat path) runs the
        forward+loss only — `pairs` comes back None and the caller
        derives gradients from `jax.vjp` over the checkpointed
        region (`_remat_microbatch_grads`)."""
        import jax.numpy as jnp

        model, opt = self.model, self.opt
        for s, v in zip(self.states, svals_c):
            s.data = v
        dev._rng_key = key_c
        opt._accum_begin(skip_backward=skip_backward)
        try:
            with autograd.layer_scope(model.name):
                out = model.train_one_batch(
                    *[tensor_mod.from_raw(b, dev) for b in mb])
        finally:
            cap = opt._accum_end()
        if len(cap) != 1:
            raise RuntimeError(
                "gradient accumulation requires train_one_batch to "
                "call backward_and_update exactly once per "
                f"microbatch; it ran {len(cap)} times")
        loss_t, pairs = cap[0]
        loss_arr = jnp.asarray(
            loss_t.data if isinstance(loss_t, Tensor) else loss_t)
        return (_unwrap_out(out), loss_arr, pairs,
                [s.data for s in self.states], dev._rng_key)

    def _discover_accum_order(self, dev, svals, key, mb_specs):
        """Learn which params receive gradients — and in what emission
        order — by abstractly evaluating ONE microbatch
        forward+backward under `jax.eval_shape` (no XLA compile, no
        execution; the same zero-cost trick as the eval_shape param
        init). The order fixes the scan carry structure. Also returns
        the abstract per-microbatch output pytree
        (jax.ShapeDtypeStruct leaves) — the sharded accumulation path
        derives its shard_map out_specs from it. All bound state is
        restored afterwards."""
        saved_s = [s.data for s in self.states]
        saved_key = dev._rng_key
        order = []

        def probe(svals_c, key_c, mb):
            outs, _, pairs, _, _ = self._run_accum_microbatch(
                dev, svals_c, key_c, mb)
            order[:] = [p for p, _ in pairs]
            return outs

        try:
            outs_sds = jax.eval_shape(probe, svals, key, mb_specs)
        finally:
            for s, v in zip(self.states, saved_s):
                s.data = v
            dev._rng_key = saved_key
        if not order:
            raise RuntimeError(
                "gradient accumulation: the backward produced no "
                "(param, grad) pairs — nothing to accumulate")
        return order, outs_sds

    def _remat_microbatch_grads(self, dev, order, svals_c, key_c, mb,
                                policy):
        """One microbatch under the scan-level remat policy (ISSUE 9):
        the ENTIRE forward+loss region — the user's train_one_batch
        with the framework backward suppressed — is wrapped in
        `jax.checkpoint(policy=...)` and gradients come from ONE
        `jax.vjp` over it, so what survives the fwd→bwd boundary is
        exactly the policy's saveable set (region inputs + e.g. dot
        results under `dots_saveable`) instead of every op's
        residuals; XLA recomputes the rest inside the backward. The
        vjp seed matches `backward_and_update`'s (the live loss scale
        under dynamic scaling, implicit ones otherwise), so the grads
        feed `apply_accumulated` identically to the captured-pairs
        path. Returns (out_arrays, loss_array, grads_in_order,
        new_state_arrays, new_key)."""
        import jax.numpy as jnp

        from . import resilience

        params = order

        def region(plist, sv, kv, mb_arrays):
            saved = [p.data for p in params]
            try:
                for p, v in zip(params, plist):
                    p.data = v
                outs, loss_arr, _, new_s, new_key = \
                    self._run_accum_microbatch(dev, sv, kv, mb_arrays,
                                               skip_backward=True)
            finally:
                for p, v in zip(params, saved):
                    p.data = v
            return loss_arr, (outs, tuple(new_s), new_key)

        ck = jax.checkpoint(region, policy=_checkpoint_policy(policy))
        plist = [p.data for p in params]
        loss_arr, vjp_fn, aux = jax.vjp(ck, plist, list(svals_c),
                                        key_c, list(mb), has_aux=True)
        outs, new_s, new_key = aux
        if resilience.guard_active() and resilience.scaler_active():
            seed = resilience.scaled_seed(loss_arr)
        else:
            seed = jnp.ones_like(loss_arr)
        grads = vjp_fn(seed)[0]
        return outs, loss_arr, list(grads), list(new_s), new_key

    def _accum_scan(self, dev, order, svals_init, key_init, micro):
        """`lax.scan` the user's train_one_batch over a [n, mb, ...]
        microbatch stack, accumulating gradients in fp32. The ONE
        definition of the accumulation loop body — the single-device
        step and the sharded shard_map local step both run exactly
        this, so the modes cannot drift apart numerically. Under
        `device.set_remat_policy` the body's gradients come from the
        checkpointed-region vjp (`_remat_microbatch_grads`) instead of
        the captured per-op walk — same accumulation math either way.
        Returns ((final_states, final_key, grad_sums, loss_sum),
        stacked_outs)."""
        import jax.numpy as jnp

        with jax.named_scope("opt/accum"):
            acc0 = [jnp.zeros(p.data.shape, jnp.float32) for p in order]
        ids = [id(p) for p in order]
        remat_pol = stats_mod.remat_policy()

        def body(carry, mb_arrays):
            svals_c, key_c, acc, loss_acc = carry
            if remat_pol is not None:
                outs, loss_arr, gl, new_s, new_key = \
                    self._remat_microbatch_grads(dev, order, svals_c,
                                                 key_c, mb_arrays,
                                                 remat_pol)
            else:
                outs, loss_arr, pairs, new_s, new_key = \
                    self._run_accum_microbatch(dev, svals_c, key_c,
                                               mb_arrays)
                gd = {id(p): (g.data if isinstance(g, Tensor) else g)
                      for p, g in pairs}
                if sorted(gd) != sorted(ids):
                    raise RuntimeError(
                        "gradient accumulation: the (param, grad) set "
                        "changed between the discovery pass and the "
                        "scan body")
                gl = [gd[i] for i in ids]
            # same sequential fp32 sum as the eager adder
            # (_accum_add_fn) — the two modes accumulate
            # bit-identically
            with jax.named_scope("opt/accum"):
                acc = [a + g.astype(jnp.float32)
                       for a, g in zip(acc, gl)]
                loss_acc = loss_acc + jnp.mean(loss_arr).astype(
                    jnp.float32)
            return (tuple(new_s), new_key, acc, loss_acc), outs

        with jax.named_scope("opt/accum"):
            carry0 = (tuple(svals_init), key_init, acc0,
                      jnp.zeros((), jnp.float32))
        if micro and int(micro[0].shape[0]) == 1:
            # Length-1 "scan" (the remat-policy reroute of a
            # non-accumulated step): run the body once inline — no
            # while loop in the HLO, so the entry-level byte/peak
            # meters stay sighted on the step's real internals.
            carry, outs = body(carry0, [m[0] for m in micro])
            with jax.named_scope("opt/accum"):
                outs = jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a)[None], outs)
            return carry, outs
        # the loop's own counter and stacking under `opt/accum`; what
        # the body traces keeps the scopes it enters itself
        with jax.named_scope("opt/accum"):
            return jax.lax.scan(body, carry0, micro)

    def _accum_step(self, n, pvals, svals, ovals, key, step_counter,
                    batch):
        """The scan-fused accumulation step body: reshape the batch to
        [n, mb, ...], `lax.scan` the user's train_one_batch over the
        microbatches — layer states (BN running stats) and the RNG key
        thread through the carry, gradients accumulate in fp32 — then
        apply the optimizer exactly once on the mean via
        `opt.apply_accumulated` (StepGuard cond, scaler unscale,
        global-norm clip, and bf16 slot quantization all fire once on
        the accumulated grads). XLA keeps the live activation/gradient
        footprint at microbatch size: only the fp32 accumulator (one
        param-sized set of arrays) persists across iterations."""
        import jax.numpy as jnp

        model, opt = self.model, self.opt
        params, states = self.params, self.states
        dev = self._device()
        saved_o = self._opt_arrays()
        saved_step = opt.step_counter
        with _bound_model(params, states, dev, pvals, svals, key):
            try:
                self._bind_opt_arrays(ovals)
                opt.step_counter = step_counter
                with jax.named_scope("opt/accum"):
                    micro = self._microbatch_stack(n, batch)
                mb = micro[0].shape[1]
                mb_specs = [jax.ShapeDtypeStruct(m.shape[1:], m.dtype)
                            for m in micro]
                order, _ = self._discover_accum_order(dev, svals, key,
                                                      mb_specs)
                (svals_f, key_f, acc, loss_sum), outs = \
                    self._accum_scan(dev, order, svals, key, micro)
                # rebind the post-scan values (the body's in-trace
                # mutations died with the scan trace)
                for s, v in zip(states, svals_f):
                    s.data = v
                dev._rng_key = key_f
                opt.apply_accumulated(loss_sum,
                                      list(zip(order, acc)), n)
                with jax.named_scope("opt/accum"):
                    out_arrays = _merge_accum_out(outs, mb)
                new_p = [p.data for p in params]
                new_s = [s.data for s in states]
                new_o = self._opt_arrays()
                new_key = dev._rng_key
                return out_arrays, new_p, new_s, new_o, new_key
            finally:
                self._bind_opt_arrays(saved_o)
                opt.step_counter = saved_step

    def _prepare_inputs(self, pvals, svals, ovals, key, batch_arrays):
        """Hook: place program inputs (sharded subclasses device_put
        onto the mesh; identity on one device)."""
        return pvals, svals, ovals, key, batch_arrays

    def _restore_key(self, new_key, dev):
        """Hook: the updated RNG key's placement. Sharded subclasses
        bring it back to the device's own placement so later eager code
        (fresh param init, dropout outside jit) stays single-device."""
        return new_key

    def _ensure_opt_slots(self):
        """Create optimizer state slots with zero arrays so the jit
        signature (flattened opt state) is stable from step one."""
        import jax.numpy as jnp

        if self.opt is None:
            return
        opt = self.opt
        base = getattr(opt, "opt", opt)  # DistOpt wraps
        from .opt import Adam, AdaGrad, RMSProp, SGD

        def zeros(name, p):
            # honors the optimizer's slot_dtype policy (byte diet):
            # half-width slots enter the jit signature half-width.
            # Born beside its param: an uncommitted jnp.zeros lives on
            # jax's default device, and (the step's outputs being
            # committed) gave step 2 a different jit signature from
            # step 1 — the whole step compiled twice (29 s of a cold
            # ResNet-50 start on the v5e).
            return jnp.zeros(p.data.shape,
                             base.slot_store_dtype(name, p),
                             device=p.data.sharding)

        for p in self.params:
            st = base.states.setdefault(id(p), {})
            if isinstance(base, SGD) and base.momentum and "momentum_buf" not in st:
                # zero buf + buf=m*buf+(1-damp)*g reproduces the lazy
                # first step (buf=g) exactly when dampening==0; with
                # dampening>0 the first graph-mode step deviates by the
                # dampening factor (documented limitation).
                st["momentum_buf"] = zeros("momentum_buf", p)
            elif isinstance(base, RMSProp) and "running_avg" not in st:
                st["running_avg"] = zeros("running_avg", p)
            elif isinstance(base, AdaGrad) and "history" not in st:
                st["history"] = zeros("history", p)
            elif isinstance(base, Adam):
                st.setdefault("m", zeros("m", p))
                st.setdefault("v", zeros("v", p))

    def lowered_text(self, *batch, optimized: bool = True) -> str:
        """HLO text of the compiled train step for these batch shapes
        (no execution, no donation hazard — .lower() only reads
        shapes). `optimized=True` (default) returns the
        post-optimization text — the input to
        `hlo_profile.bytes_accessed`, the CPU-verifiable byte-diet
        meter. `optimized=False` returns the PRE-optimization HLO
        (`dialect="hlo"`, no XLA compile paid): the text where
        `jax.checkpoint`'s optimization barriers still stand — the
        CPU backend's cleanup passes CSE the remat recompute away
        post-optimization (CPU has no HBM to save), so the remat
        knob's liveness effect (`hlo_profile.peak_bytes_estimate`) is
        only honest pre-optimization, which is also the program the
        TPU compiler (which honors the barriers) actually sees."""
        batch_arrays = tuple(
            b.data if isinstance(b, Tensor) else b for b in batch
        )
        if self._compiled is None:
            self._compiled = self._build(*batch_arrays)
        dev = self._device()
        pvals = [p.data for p in self.params]
        svals = [s.data for s in self.states]
        ovals = self._opt_arrays()
        step = 0 if self.opt is None else self.opt.step_counter
        pvals, svals, ovals, key, batch_arrays = self._prepare_inputs(
            pvals, svals, ovals, dev._rng_key, batch_arrays
        )
        lowered = self._compiled.lower(
            pvals, svals, ovals, key, step, batch_arrays
        )
        if not optimized:
            return lowered.as_text(dialect="hlo")
        return lowered.compile().as_text()

    def _note_program(self, args):
        """Hand `hlo_profile.step_programs` the step's `Lowered` at
        the current batch signature, taken once a signature just
        before its first call: the trace and the lowering are the ones
        that call needs anyway (it finds them in jax's caches), so
        this adds no work of its own. A `Lowered` holds the module and
        its compile arguments: no array, nothing of the model, so the
        program can still be read when the model is gone."""
        low = self._lowerings.get(self._batch_sig)
        if low is None:
            low = self._lowerings[self._batch_sig] = \
                self._compiled.lower(*args)
        hlo_profile.note_step_program(self, low)
        self._noted_sig = self._batch_sig

    # ---- AOT export cache (ISSUE 6) --------------------------------------
    def _export_kind(self) -> str:
        return "step"

    def _export_extras(self):
        """Hook: per-subclass key identity (the sharded step adds its
        mesh layout). None on one device."""
        return None

    def _note_batch_sig(self, batch_arrays):
        """Track the abstract batch signature across calls. Returns
        the PRIOR signature when this one is new-after-warmup (the
        retrace-storm precondition) else None — the caller fires
        `export_cache.note_step_retrace` only where a trace is
        actually imminent (plain-jit new shape, or an export-store
        MISS): a warm artifact LOAD of a new shape is not a retrace
        and must not alarm the provisioning counter."""
        sig = tuple(
            (tuple(int(d) for d in getattr(b, "shape", ())),
             str(getattr(b, "dtype", type(b).__name__)))
            for b in batch_arrays)
        prior = None
        if (self._batch_sig is not None and sig != self._batch_sig
                and sig not in self._seen_sigs):
            prior = self._batch_sig
        self._seen_sigs.add(sig)
        self._batch_sig = sig
        return prior

    def _note_warm_geometry(self, batch_arrays):
        """A warm-loaded artifact skips _build, so re-derive the
        bookkeeping _build would have done: the accumulation factor
        baked into the artifact (the key guarantees it matches the
        live knob) and its microbatch geometry counters."""
        n = self.model._accum_n() if self.opt is not None else 1
        self._accum_built = n
        if (n > 1 and batch_arrays
                and getattr(batch_arrays[0], "ndim", 0) >= 1
                and batch_arrays[0].shape[0] % n == 0):
            b = int(batch_arrays[0].shape[0])
            stats_mod.note_accum_build(n, b // n, b)

    def _obtain_export(self, args, batch_arrays, prior_sig=None):
        """Export-cache path: one executable per batch signature —
        load the serialized artifact when one exists (millisecond warm
        start, zero tracing), else trace once, serialize, and publish
        so every later process warm-starts. Falls back to the plain
        jit loudly when the program cannot be exported. `prior_sig`
        (a new-after-warmup signature's predecessor) arms the
        retrace-storm warning — fired only on a store MISS, where a
        trace is actually paid."""
        import jax as _jax

        from . import export_cache

        fn = self._by_sig.get(self._batch_sig)
        if fn is not None:
            return fn
        # The knob snapshot records the PROCESS grad_accum knob; the
        # effective factor can differ per model (compile(grad_accum=n)
        # overrides it) and bakes a different program — it must key.
        extras = {"accum": (self.model._accum_n()
                            if self.opt is not None else 1),
                  "subclass": self._export_extras()}
        key, parts = export_cache.step_key(
            self.model, self.opt, self._export_kind(), args,
            extras=extras)
        exp = export_cache.load(key)
        if exp is not None:
            self._note_warm_geometry(batch_arrays)
            fn = _jax.jit(exp.call)
        else:
            if prior_sig is not None:
                export_cache.note_step_retrace(prior_sig,
                                               self._batch_sig)
            built = self._build(*batch_arrays, donate=False)
            exp = export_cache.export_and_save(key, parts, built, args)
            fn = _jax.jit(exp.call) if exp is not None else built
        self._by_sig[self._batch_sig] = fn
        return fn

    def __call__(self, *batch: Tensor):
        """One step, in four host phases (`trace.phase`: always on a
        profiler session's host thread, in the ring while the tracer
        is on): `step.call` the whole call, `step.place` the inputs onto
        their layout, `step.enqueue` the compiled call (first call:
        trace + compile), `step.bind` the results back onto params,
        states, slots and key. Nothing here waits for the device: a
        loop waits where it reads the loss."""
        with trace_mod.phase("step.call"):
            return self._call(batch)

    def _call(self, batch):
        from . import export_cache

        batch_arrays = tuple(
            b.data if isinstance(b, Tensor) else b for b in batch
        )
        prior_sig = self._note_batch_sig(batch_arrays)
        dev = self._device()
        opt = self.opt
        exporting = export_cache.active()
        if not exporting:
            if self._from_export:
                # the store was disarmed mid-run: the held executable
                # is shape-SPECIALIZED (Exported.call rejects new
                # shapes where a polymorphic jit would retrace) —
                # rebuild plain
                self._compiled = None
                self._from_export = False
            if prior_sig is not None and self._compiled is not None:
                # the polymorphic jit is about to retrace internally
                export_cache.note_step_retrace(prior_sig,
                                               self._batch_sig)
            if self._compiled is None:
                self._compiled = self._build(*batch_arrays)
                export_cache.count_trace(0.0)
        if exporting and self._batch_sig not in self._by_sig:
            # signature must be stable before arrays are collected:
            # slots are pre-created here exactly as _build would
            self._ensure_opt_slots()
        pvals = [p.data for p in self.params]
        svals = [s.data for s in self.states]
        ovals = self._opt_arrays()
        step = 0 if opt is None else opt.step_counter
        with trace_mod.phase("step.place"):
            pvals, svals, ovals, key, batch_arrays = self._prepare_inputs(
                pvals, svals, ovals, dev._rng_key, batch_arrays
            )
        if exporting:
            self._compiled = self._obtain_export(
                (pvals, svals, ovals, key, step, batch_arrays),
                batch_arrays, prior_sig=prior_sig)
            self._from_export = True
        if self._noted_sig != self._batch_sig:
            self._note_program(
                (pvals, svals, ovals, key, step, batch_arrays))
        profiling = dev._verbosity > 0
        if profiling and getattr(self, "_hlo_rows", None) is None:
            # The step's lowering, compiled once more, yields the
            # optimized HLO for the per-op cost table (hlo_profile.py).
            try:
                self._hlo_rows = hlo_profile.profile_hlo(
                    self._lowerings[self._batch_sig].compile().as_text())
            except Exception:
                self._hlo_rows = []
        t0 = time.perf_counter() if profiling else 0.0
        with trace_mod.phase("step.enqueue"):
            out, new_p, new_s, new_o, new_key = self._compiled(
                pvals, svals, ovals, key, step, batch_arrays
            )
        # Accumulated replays count their n microbatch invocations so
        # train_steps agrees between eager and graph accumulation;
        # accum_steps counts the one executed apply (the in-trace
        # counter in apply_accumulated only fires on concrete values).
        stats_mod.count_train_step(max(1, self._accum_built))
        if self._accum_built > 1:
            stats_mod.count_accum_step()
        if profiling:
            jax.block_until_ready(new_key)
            dt = time.perf_counter() - t0
            dev.StepIteration()  # graph replay == one iteration (ref)
            dev.RecordOpTime("train_one_batch[graph]", dt)
            # Keyed per model so two compiled models on one device
            # (e.g. a GAN's G and D) keep separate tables.
            label = f"train_one_batch:{self.model.name or 'model'}" \
                    f"@{id(self.model) & 0xffff:04x}"
            prof = dev._graph_profiles.setdefault(
                label, {"rows": self._hlo_rows or [], "step_s": dt})
            prof["step_s"] = min(prof["step_s"], dt)
            prof["rows"] = self._hlo_rows or []
        with trace_mod.phase("step.bind"):
            for p, v in zip(self.params, new_p):
                p.data = v
            for s, v in zip(self.states, new_s):
                s.data = v
            self._bind_opt_arrays(new_o)
            dev._rng_key = self._restore_key(new_key, dev)
            if opt is not None:
                opt.step_counter = step + 1
            return jax.tree_util.tree_map(
                lambda a: tensor_mod.from_raw(a, dev), out
            )
