"""Stateful layers over autograd ops.

Reference parity: `python/singa/layer.py` (SINGA 3.1+ API) — `Layer`
with lazy shape-inferred parameter creation on first call, hierarchical
name scoping, `get_params/set_params` (trainable) and
`get_states/set_states` (params + non-trainable state like BN running
stats), and the layer catalogue: Linear, Conv2d, SeparableConv2d,
BatchNorm2d, MaxPool2d, AvgPool2d, Dropout, Flatten, activation
layers, Cat, Embedding. RNN/LSTM/GRU live in `singa_tpu.rnn`.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from . import autograd, initializer, tensor as tensor_mod
from .ops import native
from .tensor import Tensor


class Layer:
    """Reference: `layer.Layer`.

    Parameters are created lazily in `initialize(*inputs)` on the first
    call, so input shapes are inferred — the reference's signature
    behavior. Sublayers and params are discovered via attribute
    assignment; hierarchical names are `parent.child.param`.
    """

    sep = "."

    def __init__(self, name: Optional[str] = None):
        self.name = name or type(self).__name__
        self._initialized = False

    # -- attribute registration -------------------------------------------
    def __setattr__(self, key, value):
        if isinstance(value, Layer):
            self.__dict__.setdefault("_sublayers", OrderedDict())[key] = value
            # the name `get_params()` gives it, for `__call__`'s path
            value.__dict__["_attr"] = key
        elif isinstance(value, Tensor) and getattr(value, "stores_grad", False):
            self.__dict__.setdefault("_params", OrderedDict())[key] = value
        object.__setattr__(self, key, value)

    @property
    def sublayers(self) -> "OrderedDict[str, Layer]":
        return self.__dict__.get("_sublayers", OrderedDict())

    @property
    def own_params(self) -> "OrderedDict[str, Tensor]":
        return self.__dict__.get("_params", OrderedDict())

    # -- lifecycle ---------------------------------------------------------
    def initialize(self, *xs):
        """Create parameters from example inputs. Override in layers."""

    def forward(self, *xs):
        raise NotImplementedError

    def __call__(self, *xs):
        if not self._initialized:
            self.initialize(*xs)
            self._initialized = True
        # this instance's name joins the path that scopes the ops it
        # traces (autograd.layer_scope, inlined: every eager call of
        # every layer passes here)
        path = autograd._layer_path
        path.append(self.__dict__.get("_attr") or self.name)
        try:
            return self.forward(*xs)
        finally:
            path.pop()

    def register_param(self, attr: str, t: Tensor):
        t.requires_grad = True
        t.stores_grad = True
        setattr(self, attr, t)
        return t

    def register_state(self, attr: str, t: Tensor):
        """Non-trainable state (e.g. BN running stats)."""
        t.requires_grad = False
        t.stores_grad = False
        self.__dict__.setdefault("_state_attrs", []).append(attr)
        object.__setattr__(self, attr, t)
        return t

    # -- param / state trees ----------------------------------------------
    def get_params(self, prefix: str = "") -> Dict[str, Tensor]:
        """Reference: `Layer.get_params` — name → trainable Tensor."""
        base = prefix + self.name if prefix == "" else prefix
        out: Dict[str, Tensor] = {}
        for pname, p in self.own_params.items():
            full = base + self.sep + pname
            p.name = full
            out[full] = p
        for lname, sub in self.sublayers.items():
            out.update(sub.get_params(base + self.sep + lname))
        return out

    def set_params(self, params: Dict[str, object], prefix: str = "") -> None:
        base = prefix + self.name if prefix == "" else prefix
        for pname, p in self.own_params.items():
            full = base + self.sep + pname
            if full in params:
                v = params[full]
                p.copy_from_numpy(np.asarray(v.to_numpy() if isinstance(v, Tensor) else v))
        for lname, sub in self.sublayers.items():
            sub.set_params(params, base + self.sep + lname)

    def get_states(self, prefix: str = "") -> Dict[str, Tensor]:
        """Reference: `Layer.get_states` — params + aux state.
        Single recursion: own params + own state attrs, then descend."""
        base = prefix + self.name if prefix == "" else prefix
        out: Dict[str, Tensor] = {}
        for pname, p in self.own_params.items():
            full = base + self.sep + pname
            p.name = full
            out[full] = p
        for attr in self.__dict__.get("_state_attrs", []):
            t = getattr(self, attr)
            full = base + self.sep + attr
            t.name = full
            out[full] = t
        for lname, sub in self.sublayers.items():
            out.update(sub.get_states(base + self.sep + lname))
        return out

    def set_states(self, states: Dict[str, object], prefix: str = "") -> None:
        base = prefix + self.name if prefix == "" else prefix
        self.set_params(states, prefix)
        for attr in self.__dict__.get("_state_attrs", []):
            full = base + self.sep + attr
            if full in states:
                v = states[full]
                getattr(self, attr).copy_from_numpy(
                    np.asarray(v.to_numpy() if isinstance(v, Tensor) else v)
                )
        for lname, sub in self.sublayers.items():
            sub.set_states(states, base + self.sep + lname)

    def state_tensors(self) -> List[Tensor]:
        """Non-param state tensors (ordered) — graph-mode capture set."""
        out = [getattr(self, a) for a in self.__dict__.get("_state_attrs", [])]
        for sub in self.sublayers.values():
            out.extend(sub.state_tensors())
        return out

    def param_tensors(self) -> List[Tensor]:
        out = list(self.own_params.values())
        for sub in self.sublayers.values():
            out.extend(sub.param_tensors())
        return out


# ---------------------------------------------------------------------------
# Concrete layers
# ---------------------------------------------------------------------------
class Linear(Layer):
    """Reference: `layer.Linear(num_output, bias=True)` — in features
    inferred on first call; y = x W + b with W (in, out)."""

    def __init__(self, num_output: int, bias: bool = True, name=None):
        super().__init__(name)
        self.num_output = num_output
        self.bias = bias

    def initialize(self, x: Tensor):
        in_features = x.shape[-1]
        w = Tensor((in_features, self.num_output), device=x.device)
        initializer.he_uniform(w)
        self.register_param("W", w)
        if self.bias:
            b = Tensor((self.num_output,), device=x.device)
            b.set_value(0.0)
            self.register_param("b", b)

    def forward(self, x: Tensor):
        y = autograd.matmul(x, self.W)
        if self.bias:
            y = autograd.add_bias(y, self.b, axis=0)
        return y


class Conv2d(Layer):
    """Reference: `layer.Conv2d(nb_kernels, kernel_size, stride, padding,
    dilation, group, bias)` — NCHW, in channels inferred."""

    def __init__(self, nb_kernels: int, kernel_size, stride=1, padding=0,
                 dilation=1, group=1, bias: bool = True, name=None):
        super().__init__(name)
        self.nb_kernels = nb_kernels
        self.kernel_size = kernel_size
        self.stride = stride
        self.padding = padding
        self.dilation = dilation
        self.group = group
        self.bias = bias

    def initialize(self, x: Tensor):
        in_channels = x.shape[1]
        self.handle = native.ConvHandle(
            in_channels, self.nb_kernels, self.kernel_size,
            stride=self.stride, padding=self.padding,
            dilation=self.dilation, groups=self.group, bias=self.bias,
        )
        kh, kw = self.handle.kernel_size
        w = Tensor((self.nb_kernels, in_channels // self.group, kh, kw),
                   device=x.device)
        initializer.he_uniform(w)
        self.register_param("W", w)
        if self.bias:
            b = Tensor((self.nb_kernels,), device=x.device)
            b.set_value(0.0)
            self.register_param("b", b)

    def forward(self, x: Tensor):
        if self.bias:
            return autograd.conv2d(self.handle, x, self.W, self.b)
        return autograd.conv2d(self.handle, x, self.W)


class SeparableConv2d(Layer):
    """Reference: `layer.SeparableConv2d` — depthwise + pointwise."""

    def __init__(self, nb_kernels: int, kernel_size, stride=1, padding=0,
                 bias: bool = False, name=None):
        super().__init__(name)
        self.depthwise = None  # built at init (needs in_channels)
        self.nb_kernels = nb_kernels
        self.kernel_size = kernel_size
        self.stride = stride
        self.padding = padding
        self.bias = bias

    def initialize(self, x: Tensor):
        in_channels = x.shape[1]
        self.depthwise = Conv2d(in_channels, self.kernel_size,
                                stride=self.stride, padding=self.padding,
                                group=in_channels, bias=self.bias)
        self.pointwise = Conv2d(self.nb_kernels, 1, bias=self.bias)

    def forward(self, x: Tensor):
        return self.pointwise(self.depthwise(x))


class BatchNorm2d(Layer):
    """Reference: `layer.BatchNorm2d(momentum=0.9)`.

    NOTE on momentum semantics: SINGA passes `momentum` to cuDNN as
    exponentialAverageFactor, i.e. running = (1-m)*running + m*batch.
    """

    def __init__(self, momentum: float = 0.9, eps: float = 1e-5, name=None):
        super().__init__(name)
        self.momentum = momentum
        self.eps = eps

    def initialize(self, x: Tensor):
        c = x.shape[1]
        self.handle = native.BatchNormHandle(factor=self.momentum, eps=self.eps)
        scale = Tensor((c,), device=x.device)
        scale.set_value(1.0)
        self.register_param("scale", scale)
        bias = Tensor((c,), device=x.device)
        bias.set_value(0.0)
        self.register_param("bias", bias)
        rm = Tensor((c,), device=x.device)
        rm.set_value(0.0)
        self.register_state("running_mean", rm)
        rv = Tensor((c,), device=x.device)
        rv.set_value(1.0)
        self.register_state("running_var", rv)

    def forward(self, x: Tensor):
        op = autograd._BatchNorm2d(self.handle, self.running_mean,
                                   self.running_var)
        y = op(x, self.scale, self.bias)
        if autograd.training and op.new_running_mean is not None:
            # Rebind state (reference mutates in cuDNN); in graph mode
            # these become traced outputs captured by Model.compile.
            self.running_mean.data = op.new_running_mean
            self.running_var.data = op.new_running_var
        return y


class Pooling2d(Layer):
    def __init__(self, kernel_size, stride=None, padding=0, is_max=True,
                 name=None):
        super().__init__(name)
        self.handle = native.PoolingHandle(kernel_size, stride=stride,
                                           padding=padding, is_max=is_max)

    def forward(self, x: Tensor):
        return autograd.pooling_2d(self.handle, x)


class MaxPool2d(Pooling2d):
    """Reference: `layer.MaxPool2d`."""

    def __init__(self, kernel_size, stride=None, padding=0, name=None):
        super().__init__(kernel_size, stride, padding, is_max=True, name=name)


class AvgPool2d(Pooling2d):
    """Reference: `layer.AvgPool2d`."""

    def __init__(self, kernel_size, stride=None, padding=0, name=None):
        super().__init__(kernel_size, stride, padding, is_max=False, name=name)


class Dropout(Layer):
    """Reference: `layer.Dropout(ratio)`."""

    def __init__(self, ratio: float = 0.5, name=None):
        super().__init__(name)
        self.ratio = ratio

    def forward(self, x: Tensor):
        # Key comes from the *input's* device each call (never cached:
        # params may migrate after a host-side init forward).
        key = (x.device.next_key()
               if autograd.training and self.ratio > 0.0 else None)
        return autograd.Dropout(self.ratio, rng_key=key)(x)


class Flatten(Layer):
    """Reference: `layer.Flatten(axis=1)`."""

    def __init__(self, axis: int = 1, name=None):
        super().__init__(name)
        self.axis = axis

    def forward(self, x: Tensor):
        return autograd.flatten(x, self.axis)


class ReLU(Layer):
    def forward(self, x):
        return autograd.relu(x)


class Sigmoid(Layer):
    def forward(self, x):
        return autograd.sigmoid(x)


class Tanh(Layer):
    def forward(self, x):
        return autograd.tanh(x)


class Softmax(Layer):
    def __init__(self, axis: int = 1, name=None):
        super().__init__(name)
        self.axis = axis

    def forward(self, x):
        return autograd.softmax(x, self.axis)


class LeakyReLU(Layer):
    def __init__(self, negative_slope: float = 0.01, name=None):
        super().__init__(name)
        self.a = negative_slope

    def forward(self, x):
        return autograd.LeakyRelu(self.a)(x)


class Gelu(Layer):
    def forward(self, x):
        return autograd.Gelu()(x)


class Cat(Layer):
    """Reference: `layer.Cat(axis)`."""

    def __init__(self, axis: int = 0, name=None):
        super().__init__(name)
        self.axis = axis

    def forward(self, *xs):
        return autograd.cat(list(xs), self.axis)


class Embedding(Layer):
    """Reference: `layer.Embedding(input_dim, output_dim)` — lookup
    table, rows selected by int indices."""

    def __init__(self, input_dim: int, output_dim: int, name=None):
        super().__init__(name)
        self.input_dim = input_dim
        self.output_dim = output_dim

    def initialize(self, x: Tensor):
        w = Tensor((self.input_dim, self.output_dim), device=x.device)
        initializer.gaussian(w, 0.0, 0.05)
        self.register_param("W", w)

    def forward(self, x: Tensor):
        return autograd.embedding(self.W, x)


class LayerNorm(Layer):
    """LayerNorm over the trailing dim; params gamma/beta (lazy)."""

    def __init__(self, eps: float = 1e-5, name=None):
        super().__init__(name)
        self.eps = eps

    def initialize(self, x: Tensor):
        d = x.shape[-1]
        g = Tensor((d,), device=x.device)
        b = Tensor((d,), device=x.device)
        initializer.constant(g, 1.0)
        initializer.constant(b, 0.0)
        self.register_param("gamma", g)
        self.register_param("beta", b)

    def forward(self, x: Tensor):
        return autograd.layer_norm(x, self.gamma, self.beta, self.eps)


class RMSNorm(Layer):
    """Root-mean-square norm (no reference equivalent; the modern-LM
    alternative to LayerNorm). Composed from primitive autograd ops so
    backward and ONNX export (Mul/ReduceMean/Add/Sqrt/Div) come from
    the existing mappings — XLA fuses the chain in graph mode."""

    def __init__(self, eps: float = 1e-6, name=None):
        super().__init__(name)
        self.eps = eps

    def initialize(self, x: Tensor):
        d = x.shape[-1]
        g = Tensor((d,), device=x.device)
        initializer.constant(g, 1.0)
        self.register_param("gamma", g)

    def forward(self, x: Tensor):
        ms = autograd.ReduceMean(axes=[-1], keepdims=True)(
            autograd.mul(x, x))
        # eps passed as a python scalar per call (ops coerce it);
        # caching a constant TENSOR here is a trap — initialize/forward
        # may run inside a jit trace (Model.compile's init forward) and
        # a cached tracer-backed value would leak out of the trace
        rms = autograd.Sqrt()(autograd.add(ms, np.float32(self.eps)))
        return autograd.mul(autograd.div(x, rms), self.gamma)


class MultiHeadAttention(Layer):
    """Multi-head self-attention (no reference equivalent — SINGA's
    attention models arrive only via ONNX import). TPU-first: per-head
    projections stay one fused GEMM on the MXU; with `mesh` carrying a
    "seq" axis the score/softmax/value core runs as ring attention
    (sequence parallelism), and the q/k/v/o projections pick up tensor
    parallelism from the param sharding rules ("model" axis)."""

    def __init__(self, num_heads: int, causal: bool = True, mesh=None,
                 dropout: float = 0.0, name=None):
        super().__init__(name)
        self.num_heads = num_heads
        self.causal = causal
        self.mesh = mesh
        self.q_proj = Linear(0)  # lazy: sized to d_model on first call
        self.k_proj = Linear(0)
        self.v_proj = Linear(0)
        self.o_proj = Linear(0)
        self.drop = Dropout(dropout) if dropout else None

    def initialize(self, x: Tensor):
        d_model = x.shape[-1]
        if d_model % self.num_heads:
            raise ValueError(
                f"d_model {d_model} not divisible by heads {self.num_heads}")
        for proj in (self.q_proj, self.k_proj, self.v_proj, self.o_proj):
            proj.num_output = d_model

    def forward(self, x: Tensor):
        B, S, E = x.shape
        H = self.num_heads
        D = E // H

        def split(t):  # [B,S,E] -> [B,H,S,D]
            t = autograd.reshape(t, (B, S, H, D))
            return autograd.transpose(t, (0, 2, 1, 3))

        q = split(self.q_proj(x))
        k = split(self.k_proj(x))
        v = split(self.v_proj(x))
        o = autograd.attention(q, k, v, causal=self.causal, mesh=self.mesh)
        o = autograd.transpose(o, (0, 2, 1, 3))
        o = autograd.reshape(o, (B, S, E))
        o = self.o_proj(o)
        return self.drop(o) if self.drop is not None else o


class Sequential(Layer):
    """Convenience container (reference builds these ad hoc)."""

    def __init__(self, *layers, name=None):
        super().__init__(name)
        for i, l in enumerate(layers):
            setattr(self, f"l{i}", l)
        self._seq = list(layers)

    def forward(self, x):
        for l in self._seq:
            x = l(x)
        return x


class MoE(Layer):
    """Trainable top-1 mixture-of-experts FFN (ISSUE 10; no reference
    equivalent — the GShard recipe of `parallel/moe.py` as a first-
    class layer). Params: replicated router `gate` (D, E) plus
    expert-stacked `w1`/`b1`/`w2`/`b2` whose leading expert dim the
    default sharding rules place on the mesh's "expert" axis, so a
    `ParallelPlan(expert=n)` shards expert compute across chips with
    GSPMD inserting the dispatch/combine all-to-alls.

    The auxiliary load-balancing loss of the LAST forward is exposed
    as `self.aux_loss` (a Tensor; add `aux_weight * layer.aux_loss`
    into the training loss — gradients flow through the router).
    BN-style state: `dropped_frac` holds an exponential moving average
    of the fraction of tokens dropped by expert-capacity overflow,
    updated only in training mode and captured as a program output in
    graph mode exactly like BatchNorm running stats.

    `capacity_factor=None` defers to the compile-time plan
    (`ParallelPlan.moe_capacity_factor`, default 1.25); the process
    knob `stats.moe_capacity_factor` — the autotuner's axis —
    overrides both at trace time."""

    def __init__(self, num_experts: int, d_ff: int,
                 capacity_factor: Optional[float] = None,
                 momentum: float = 0.9, mesh=None,
                 axis_name: str = "expert", name=None):
        super().__init__(name)
        self.num_experts = int(num_experts)
        self.d_ff = int(d_ff)
        self.capacity_factor = capacity_factor
        self.momentum = float(momentum)
        self.mesh = mesh
        self.axis_name = axis_name
        # which attrs the USER pinned at construction: plan wiring
        # only fills the others, and a RE-compile with a different
        # plan re-fills them (the set_grad_accum re-compile contract
        # — first-plan values must not stick)
        self._own_mesh = mesh is not None
        self._own_cf = capacity_factor is not None

    def _apply_plan(self, plan, mesh):
        if not self._own_mesh:
            self.mesh = mesh
        if not self._own_cf:
            self.capacity_factor = plan.moe_capacity_factor

    def initialize(self, x: Tensor):
        d = x.shape[-1]
        e, f = self.num_experts, self.d_ff
        s1, s2 = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
        gate = Tensor((d, e), device=x.device)
        initializer.gaussian(gate, 0.0, s1)
        self.register_param("gate", gate)
        w1 = Tensor((e, d, f), device=x.device)
        initializer.gaussian(w1, 0.0, s1)
        self.register_param("w1", w1)
        b1 = Tensor((e, f), device=x.device)
        b1.set_value(0.0)
        self.register_param("b1", b1)
        w2 = Tensor((e, f, d), device=x.device)
        initializer.gaussian(w2, 0.0, s2)
        self.register_param("w2", w2)
        b2 = Tensor((e, d), device=x.device)
        b2.set_value(0.0)
        self.register_param("b2", b2)
        df = Tensor((), device=x.device)
        df.set_value(0.0)
        self.register_state("dropped_frac", df)

    def forward(self, x: Tensor):
        import jax

        cf = self.capacity_factor if self.capacity_factor else 1.25
        y, aux, dropped = autograd.moe_ffn(
            x, self.gate, self.w1, self.b1, self.w2, self.b2,
            capacity_factor=cf, mesh=self.mesh,
            axis_name=self.axis_name)
        self.aux_loss = aux
        if autograd.training:
            # BN-style EMA rebind (raw arrays — state is non-grad; in
            # graph mode the new value is captured as a program
            # output, the BatchNorm contract)
            import jax.numpy as jnp

            m = self.momentum
            old = jnp.asarray(self.dropped_frac.data)
            new = ((1.0 - m) * old
                   + m * jnp.asarray(dropped.data).astype(old.dtype))
            self.dropped_frac.data = new
            from . import stats as stats_mod

            if not isinstance(dropped.data, jax.core.Tracer):
                stats_mod.note_moe_dropped(float(dropped.data))
        return y


class PipelineStack(Layer):
    """Homogeneous stack of pipeline stages (ISSUE 10; no reference
    equivalent). Holds P stages' parameters STACKED on a leading
    stage dim (registered as `stage_<leaf>` params, which the default
    sharding rules place on the mesh's "pipe" axis — chip i holds
    stage i), and runs `y = stage_{P-1}(...stage_0(x))`:

      * under a mesh whose "pipe" axis is >1 (a `ParallelPlan` with
        `pipe=n`): as a 1F1B (default) or GPipe schedule inside the
        compiled step (`parallel/pipeline.py`), microbatches threaded
        from the plan / the process knob;
      * otherwise (eager steps, single-device graphs, the lazy-init
        forward): as the bit-identical sequential composition.

    `stage_fn(params_dict, h) -> h` must be pure jax with output
    shape == input shape (homogeneous pipeline);
    `init_stage(key, x_shape) -> {leaf: array}` draws one stage's
    parameters from a PRNG key. `PipelineStack.mlp(...)` builds the
    canonical residual-GELU-MLP block stack."""

    def __init__(self, num_stages: int, stage_fn, init_stage, *,
                 mesh=None, axis_name: str = "pipe",
                 microbatches: Optional[int] = None,
                 schedule: Optional[str] = None, batch_axis=None,
                 name=None):
        super().__init__(name)
        self.num_stages = int(num_stages)
        if self.num_stages < 1:
            raise ValueError("PipelineStack needs num_stages >= 1")
        self._stage_fn = stage_fn
        self._init_stage = init_stage
        # stage_fn identity as a SCALAR config attr: the topology
        # fingerprint only hashes scalar layer config, and two stacks
        # with different stage math but identical param shapes must
        # never share an AOT artifact. Bytecode alone is NOT enough —
        # constants live in co_consts and factory-captured values in
        # closure cells (two `lambda p, h: h + c * (h @ p['W'])` with
        # different c share co_code) — so fold both in via the
        # export-cache scalarizer.
        import hashlib
        import json as _json

        from . import export_cache as _ec

        cells = []
        for c in getattr(stage_fn, "__closure__", None) or ():
            try:
                cells.append(_ec._scalarize(c.cell_contents, 1))
            except Exception:
                cells.append(type(c.cell_contents).__name__)
        self._stage_fn_id = hashlib.sha256(_json.dumps(
            [_ec._scalarize(stage_fn), cells], sort_keys=True,
            default=str).encode()).hexdigest()[:16]
        self.mesh = mesh
        self.axis_name = axis_name
        self.microbatches = microbatches
        self.schedule = schedule
        self.batch_axis = batch_axis
        # user-pinned ctor attrs (see MoE._apply_plan): plan wiring
        # fills the rest and RE-fills them on re-compile with a
        # different plan
        self._own_mesh = mesh is not None
        self._own_mb = microbatches is not None
        self._own_schedule = schedule is not None

    def _apply_plan(self, plan, mesh):
        if not self._own_mesh:
            self.mesh = mesh
        if not self._own_mb:
            self.microbatches = plan.pipeline_microbatches
        if not self._own_schedule:
            self.schedule = plan.pipeline_schedule

    @classmethod
    def mlp(cls, num_stages: int, d_ff: Optional[int] = None, **kw):
        """Residual pre-activation GELU MLP blocks:
        h + gelu(h W1 + b1) W2 + b2, with d_ff defaulting to 2*d."""
        import jax
        import jax.numpy as jnp

        def stage_fn(p, h):
            return h + jax.nn.gelu(h @ p["W1"] + p["b1"]) @ p["W2"] \
                + p["b2"]

        def init_stage(key, x_shape):
            d = int(x_shape[-1])
            f = d_ff or 2 * d
            k1, k2 = jax.random.split(key)
            s1, s2 = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
            return {
                "W1": (jax.random.normal(k1, (d, f)) * s1
                       ).astype(jnp.float32),
                "b1": jnp.zeros((f,), jnp.float32),
                "W2": (jax.random.normal(k2, (f, d)) * s2
                       ).astype(jnp.float32),
                "b2": jnp.zeros((d,), jnp.float32),
            }

        return cls(num_stages, stage_fn, init_stage, **kw)

    def initialize(self, x: Tensor):
        import jax
        import jax.numpy as jnp

        dev = x.device
        # compile-time eval: init draws from CONCRETE keys even under
        # the eval_shape init forward (device.next_key's contract), so
        # no tracer can leak into the registered params
        with jax.ensure_compile_time_eval():
            per_stage = []
            for _ in range(self.num_stages):
                per_stage.append(
                    self._init_stage(dev.next_key(), tuple(x.shape)))
            names = sorted(per_stage[0])
            stacks = {nm: jnp.stack([jnp.asarray(st[nm])
                                     for st in per_stage])
                      for nm in names}
        for nm in names:
            t = tensor_mod.from_raw(stacks[nm], dev)
            self.register_param(f"stage_{nm}", t)
        self._leaf_names = tuple(names)

    def forward(self, x: Tensor):
        leaves = [getattr(self, f"stage_{nm}")
                  for nm in self._leaf_names]
        op = autograd.PipelineApply(
            self._stage_fn, self._leaf_names, self.num_stages,
            mesh=self.mesh, axis_name=self.axis_name,
            microbatches=self.microbatches,
            schedule=self.schedule or "1f1b",
            batch_axis=self.batch_axis)
        return op(x, *leaves)
