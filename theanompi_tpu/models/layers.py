"""Layer library.

TPU-native rebuild of Theano-MPI's ``theanompi/models/layers2.py``
(SURVEY.md §2.7): ``Weight`` (init schemes + ``.npy`` save/load), ``Conv``
(was cuDNN, now ``lax.conv_general_dilated`` lowered onto the MXU), ``Pool``,
``LRN``, ``FC``, ``Dropout`` (train/test switch), ``Softmax``, ``BatchNorm``,
and input mean-subtraction handling.

Design departures from the reference, all deliberate and TPU-first:

* **NHWC layout** (reference was Theano's bc01/NCHW): XLA:TPU's native conv
  layout, keeps the channel dim in the lane dimension of the VPU/MXU tiles.
* **Pure pytrees, no shared variables**: a layer is a small object holding
  static hyperparameters; ``init(key)`` returns its parameter pytree and
  ``apply(params, x, ...)`` is pure, so the whole model jits and shards.
* **Mixed precision hook**: every layer takes ``compute_dtype`` — params stay
  float32, matmul/conv inputs are cast (bfloat16 on TPU) with float32
  accumulation via ``preferred_element_type``.
* **BatchNorm state** (running stats) is threaded as a separate ``state``
  pytree through :class:`Sequential` rather than mutated in place.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..jax_compat import vary
from ..utils import telemetry


# ---------------------------------------------------------------------------
# Weight: init schemes + save/load  (reference: layers2.Weight)
# ---------------------------------------------------------------------------

def init_weight(key, shape: Sequence[int], scheme: Union[str, Tuple[str, float]],
                dtype=jnp.float32) -> jnp.ndarray:
    """Initialize one weight array.

    Scheme forms (matching the reference's ``Weight`` modes):
      ``('normal', std)``   gaussian, the AlexNet-era default (std 0.01/0.005)
      ``('constant', c)``   constant fill (bias init 0 / 0.1 / 1)
      ``'xavier'``          Glorot uniform
      ``'he'``              He normal (fan-in), for ReLU nets
    """
    if isinstance(scheme, tuple):
        kind, arg = scheme
    else:
        kind, arg = scheme, None
    if kind == "normal":
        std = 0.01 if arg is None else arg
        return std * jax.random.normal(key, shape, dtype)
    if kind == "constant":
        c = 0.0 if arg is None else arg
        return jnp.full(shape, c, dtype)
    fan_in, fan_out = _fans(shape)
    if kind == "xavier":
        limit = math.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(key, shape, dtype, -limit, limit)
    if kind == "he":
        std = math.sqrt(2.0 / fan_in)
        return std * jax.random.normal(key, shape, dtype)
    raise ValueError(f"unknown init scheme {kind!r}")


def _fans(shape: Sequence[int]) -> Tuple[int, int]:
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv HWIO: receptive field * in, receptive field * out
    rf = int(np.prod(shape[:-2]))
    return rf * shape[-2], rf * shape[-1]


# ---------------------------------------------------------------------------
# Layer base + Sequential
# ---------------------------------------------------------------------------

class Layer:
    """Base layer: static hyperparams on the object, params/state as pytrees."""

    name: str = "layer"
    has_state: bool = False  # True for BatchNorm (running stats)

    def init(self, key) -> Any:
        return None

    def init_state(self) -> Any:
        return None

    def apply(self, params, x, *, train: bool = False, rng=None, state=None):
        """Returns ``(y, new_state)``; ``new_state`` is None for stateless layers."""
        raise NotImplementedError

    def kept_bytes(self, tokens: int) -> int:
        """Bytes of this layer's products named in :data:`KEPT_PRODUCTS`
        over ``tokens`` rows: what a checkpoint that saves those names
        keeps of it.  None here."""
        return 0

    def __repr__(self):
        return f"{type(self).__name__}({self.name})"


class Sequential:
    """Composes layers; params/state are dicts keyed by unique layer names.

    Reference equivalent: the explicit layer lists each model file built and
    iterated over (``layers2`` usage in ``alex_net.py`` etc.).
    """

    def __init__(self, layers: List[Layer]):
        self.layers = layers
        seen: Dict[str, int] = {}
        self._keys = []
        for l in layers:
            n = l.name
            if n in seen:
                seen[n] += 1
                n = f"{n}_{seen[l.name]}"
            else:
                seen[n] = 0
            self._keys.append(n)
        # A ReLU layer directly before a max Pool runs as pre-activation ->
        # pool -> ReLU.  relu(maxpool(x)) == maxpool(relu(x)) bit for bit,
        # gradient included (max commutes with a non-decreasing function),
        # but the max-pool backward (select-and-scatter) takes its operand
        # unfused: fed the post-ReLU tensor it makes the compiler keep that
        # beside the pre-activation every other consumer re-ReLUs on the
        # fly — a second copy of every pooled feature map (PERF.md §6).
        self._pool_first = frozenset(
            i for i, (a, b) in enumerate(zip(layers, layers[1:]))
            if isinstance(a, _Affine) and a.activation == "relu"
            and isinstance(b, Pool) and b.mode == "max")
        telemetry.count("pool_before_relu", len(self._pool_first))

    def init(self, key) -> Dict[str, Any]:
        params = {}
        for k, layer in zip(self._keys, self.layers):
            key, sub = jax.random.split(key)
            p = layer.init(sub)
            if p is not None:
                params[k] = p
        return params

    def init_state(self) -> Dict[str, Any]:
        state = {}
        for k, layer in zip(self._keys, self.layers):
            s = layer.init_state()
            if s is not None:
                state[k] = s
        return state

    def apply(self, params, x, *, train=False, rng=None, state=None):
        state = state or {}
        new_state = dict(state)
        for i, (k, layer) in enumerate(zip(self._keys, self.layers)):
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            # the layer's key names its instructions in the compiled
            # program (`.../conv3_1/conv_general_dilated`)
            with jax.named_scope(k):
                if i in self._pool_first:
                    x = layer.pre_activation(params[k], x)
                    continue
                y = layer.apply(params.get(k), x, train=train, rng=sub,
                                state=state.get(k))
                if layer.has_state:
                    x, st = y
                    if st is not None:
                        new_state[k] = st
                else:
                    x = y if not isinstance(y, tuple) else y[0]
                if i - 1 in self._pool_first:
                    x = jax.nn.relu(x)
        return x, new_state


class _Affine(Layer):
    """``activation(pre_activation(x))``: :class:`Conv`,
    :class:`ConvTranspose`, :class:`FC`.  :class:`Sequential` calls the two
    halves apart where a max pool belongs between them."""

    activation: Optional[str] = None

    def pre_activation(self, params, x):
        raise NotImplementedError

    def apply(self, params, x, *, train=False, rng=None, state=None):
        return _activate(self.pre_activation(params, x), self.activation)


# ---------------------------------------------------------------------------
# Conv  (reference: layers2.Conv on cuDNN; here lax conv on the MXU)
# ---------------------------------------------------------------------------

class Conv(_Affine):
    def __init__(self, in_ch: int, out_ch: int, kernel: Union[int, Tuple[int, int]],
                 stride: Union[int, Tuple[int, int]] = 1,
                 padding: Union[str, int] = "SAME",
                 groups: int = 1,
                 w_init=("normal", 0.01), b_init=("constant", 0.0),
                 activation: Optional[str] = "relu",
                 compute_dtype=jnp.bfloat16, name: str = "conv"):
        self.in_ch, self.out_ch = in_ch, out_ch
        self.kernel = (kernel, kernel) if isinstance(kernel, int) else tuple(kernel)
        self.stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
        if isinstance(padding, int):
            self.padding = [(padding, padding), (padding, padding)]
        else:
            self.padding = padding
        self.groups = groups  # AlexNet's historical 2-group convs
        self.w_init, self.b_init = w_init, b_init
        self.activation = activation
        self.compute_dtype = compute_dtype
        self.name = name

    def init(self, key):
        kh, kw = self.kernel
        kw_key, b_key = jax.random.split(key)
        w = init_weight(kw_key, (kh, kw, self.in_ch // self.groups, self.out_ch),
                        self.w_init)
        b = init_weight(b_key, (self.out_ch,), self.b_init)
        return {"w": w, "b": b}

    def pre_activation(self, params, x):
        cd = self.compute_dtype
        # No preferred_element_type here: with bf16 operands the MXU still
        # accumulates in fp32 internally, and requesting an fp32 output breaks
        # the conv transpose (bf16 kernel vs fp32 cotangent) in jax 0.9.
        y = jax.lax.conv_general_dilated(
            x.astype(cd), params["w"].astype(cd),
            window_strides=self.stride, padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=self.groups,
        )
        return y + params["b"].astype(cd)


class ConvTranspose(_Affine):
    """Transposed (fractionally-strided) convolution — the DCGAN-style
    generator upsampler used by the reference's GAN models
    (``theanompi/models/wgan.py`` / ``lsgan.py``, SURVEY.md §2.7).  Lowered
    via ``lax.conv_transpose`` onto the MXU."""

    def __init__(self, in_ch: int, out_ch: int, kernel: Union[int, Tuple[int, int]],
                 stride: Union[int, Tuple[int, int]] = 2,
                 padding: str = "SAME",
                 w_init=("normal", 0.02), b_init=("constant", 0.0),
                 activation: Optional[str] = "relu",
                 compute_dtype=jnp.bfloat16, name: str = "deconv"):
        self.in_ch, self.out_ch = in_ch, out_ch
        self.kernel = (kernel, kernel) if isinstance(kernel, int) else tuple(kernel)
        self.stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
        self.padding = padding
        self.w_init, self.b_init = w_init, b_init
        self.activation = activation
        self.compute_dtype = compute_dtype
        self.name = name

    def init(self, key):
        kh, kw = self.kernel
        kw_key, b_key = jax.random.split(key)
        w = init_weight(kw_key, (kh, kw, self.in_ch, self.out_ch), self.w_init)
        b = init_weight(b_key, (self.out_ch,), self.b_init)
        return {"w": w, "b": b}

    def pre_activation(self, params, x):
        cd = self.compute_dtype
        y = jax.lax.conv_transpose(
            x.astype(cd), params["w"].astype(cd),
            strides=self.stride, padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y + params["b"].astype(cd)


class FC(_Affine):
    """Fully connected layer (reference: layers2.FC / Softmax head matmul)."""

    def __init__(self, n_in: int, n_out: int,
                 w_init=("normal", 0.005), b_init=("constant", 0.0),
                 activation: Optional[str] = "relu",
                 compute_dtype=jnp.bfloat16, name: str = "fc"):
        self.n_in, self.n_out = n_in, n_out
        self.w_init, self.b_init = w_init, b_init
        self.activation = activation
        self.compute_dtype = compute_dtype
        self.name = name

    def init(self, key):
        kw, kb = jax.random.split(key)
        return {"w": init_weight(kw, (self.n_in, self.n_out), self.w_init),
                "b": init_weight(kb, (self.n_out,), self.b_init)}

    def pre_activation(self, params, x):
        cd = self.compute_dtype
        g = _gathered
        if g is not None and x.ndim == 2 and g.take(
                params["w"], x.shape[0], jnp.dtype(cd).itemsize):
            y = _dot_gathered(x.astype(cd), params["w"], g.axis)
        else:
            y = jnp.dot(x.astype(cd), params["w"].astype(cd))
        return y + params["b"].astype(cd)


# -- a wide FC's weight gradient from gathered operands ----------------------
# The rule and its arithmetic: parallel/strategies.py ``gather_engages``.

class GatheredGrads:
    """One BSP step's record of the ``FC`` weights whose gradient is formed
    from all-gathered operands; ``BSP_Exchanger.gathered_grads`` makes it,
    ``steps.one_step`` traces the loss through :meth:`loss`, and ``taken``
    then names the leaves that reach ``step_update`` already summed over
    the workers.  A weight is known by being the very leaf of the
    parameter tree the loss was handed: a model that copies or casts its
    tree first keeps the all-reduce.  (A taken leaf must have no reader
    beside its ``FC``: a tied copy's local gradient would go unsummed.)"""

    def __init__(self, axis: str, engages):
        self.axis = axis
        self.engages = engages          # (rows, n_in, n_out, itemsize) -> bool
        self.taken: Dict[str, tuple] = {}
        self._paths: Dict[int, str] = {}

    def loss(self, loss_and_metrics):
        def watched(params, *args):
            global _gathered
            self._paths = {
                id(leaf): jax.tree_util.keystr(path) for path, leaf
                in jax.tree_util.tree_flatten_with_path(params)[0]}
            before, _gathered = _gathered, self
            try:
                return loss_and_metrics(params, *args)
            finally:
                _gathered, self._paths = before, {}
        return watched

    def take(self, w, rows: int, itemsize: int) -> bool:
        path = self._paths.get(id(w))
        if path is None or w.ndim != 2 or w.dtype != jnp.float32 \
                or not self.engages(rows, *w.shape, itemsize):
            return False
        self.taken[path] = (rows,) + tuple(w.shape) + (itemsize,)
        return True


_gathered: Optional[GatheredGrads] = None   # open while its loss is traced


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dot_gathered(x, w, axis):
    """``x . w`` in ``x``'s dtype whose backward returns ``w``'s gradient
    SUMMED over ``axis``: both operands all-gathered (in the dtype they
    have as matmul operands anyway), one full-batch product accumulated in
    ``w``'s float32.  Every chip multiplies the same operands in the same
    order, so replicas stay bit-identical; ``dx`` stays local."""
    return jnp.dot(x, w.astype(x.dtype))


def _dot_gathered_fwd(x, w, axis):
    return _dot_gathered(x, w, axis), (x, w)


def _dot_gathered_bwd(axis, res, dy):
    x, w = res
    dx = jnp.dot(dy, w.astype(dy.dtype).T)
    with jax.named_scope("exchange"):
        rows = [jax.lax.all_gather(a, axis, tiled=True) for a in (x, dy)]
    dw = jax.lax.dot_general(*rows, (((0,), (0,)), ((), ())),
                             preferred_element_type=w.dtype)
    return dx, dw


_dot_gathered.defvjp(_dot_gathered_fwd, _dot_gathered_bwd)


class Pool(Layer):
    """Max/avg pooling via ``lax.reduce_window`` (reference: layers2.Pool)."""

    def __init__(self, size: Union[int, Tuple[int, int]] = 2,
                 stride: Optional[Union[int, Tuple[int, int]]] = None,
                 mode: str = "max", padding: str = "VALID", name: str = "pool"):
        self.size = (size, size) if isinstance(size, int) else tuple(size)
        stride = stride if stride is not None else self.size
        self.stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
        self.mode = mode
        self.padding = padding
        self.name = name

    def apply(self, params, x, *, train=False, rng=None, state=None):
        window = (1,) + self.size + (1,)
        strides = (1,) + self.stride + (1,)
        if self.mode == "max":
            return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window, strides,
                                         self.padding)
        s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, self.padding)
        if self.padding == "VALID":
            return s / (self.size[0] * self.size[1])
        ones = jnp.ones_like(x)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides,
                                       self.padding)
        return s / counts


@functools.lru_cache(maxsize=None)
def _lrn_band(c: int, n: int) -> np.ndarray:
    """[c, c] 0/1 matrix whose column i sums the n-channel window at i."""
    half = n // 2
    band = np.zeros((c, c), np.float32)
    for i in range(c):
        band[max(0, i - half):i + half + 1, i] = 1.0
    return band


class LRN(Layer):
    """Cross-channel local response normalization (AlexNet-era; reference
    layers2.LRN):  b = a / (k + alpha/n * sum_{window} a^2)^beta.

    TPU mapping: the 5-tap cross-channel sum runs as a 1×1 conv against a
    constant banded matrix on the input's native NHWC shape — the channel
    dim is the lane dim on TPU, where a sliding ``reduce_window`` is slow,
    but a tiny matmul rides the MXU and its gradient is the same
    (symmetric) band conv.  Accumulation is fp32.  For β=0.75 the power is
    composed from ``rsqrt``/``sqrt`` (d^-0.75 = rsqrt(d)·sqrt(rsqrt(d)))
    instead of a transcendental pow.
    """

    def __init__(self, n: int = 5, k: float = 2.0, alpha: float = 1e-4,
                 beta: float = 0.75, name: str = "lrn"):
        self.n, self.k, self.alpha, self.beta = n, k, alpha, beta
        self.name = name

    def apply(self, params, x, *, train=False, rng=None, state=None):
        c = x.shape[-1]
        x4 = x if x.ndim == 4 else x.reshape(1, -1, 1, c)
        xf = x4.astype(jnp.float32)
        ssum = jax.lax.conv_general_dilated(
            jnp.square(xf),
            jnp.asarray(_lrn_band(c, self.n)).reshape(1, 1, c, c),
            (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        d = self.k + (self.alpha / self.n) * ssum
        if self.beta == 0.75:
            inv = jax.lax.rsqrt(d)
            scale = inv * jnp.sqrt(inv)
        else:
            scale = jnp.exp(-self.beta * jnp.log(d))
        return (xf * scale).astype(x.dtype).reshape(x.shape)


class Dropout(Layer):
    """Train/test-switched dropout (reference: layers2.Dropout)."""

    def __init__(self, rate: float = 0.5, name: str = "dropout"):
        self.rate = rate
        self.name = name

    def apply(self, params, x, *, train=False, rng=None, state=None):
        if not train or self.rate == 0.0:
            return x
        assert rng is not None, "Dropout in train mode needs an rng"
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0)


class BatchNorm(Layer):
    """Batch normalization with running stats (reference: layers2.BatchNorm).

    Train mode uses batch statistics and returns updated running stats in the
    state pytree; eval mode uses running stats.  Normalizes over all axes but
    the last (NHWC channel).

    ``norm_dtype``: dtype of the normalize arithmetic.  ``None`` (default)
    upcasts activations to fp32 end to end.  ``bfloat16`` keeps the STAT
    math fp32 (reductions upcast on read, which XLA fuses into the producer)
    but folds (mean, inv·scale, bias) into per-channel bf16 vectors and
    normalizes in bf16 — no fp32 activation tensor is materialized between
    bf16 convs.  A/B lever for the BN share of ResNet-50 step time
    (never set against the default on a chip: ROADMAP S8)."""

    has_state = True

    def __init__(self, n_ch: int, momentum: float = 0.9, eps: float = 1e-5,
                 norm_dtype=None, name: str = "bn"):
        self.n_ch, self.momentum, self.eps = n_ch, momentum, eps
        self.norm_dtype = norm_dtype
        self.name = name

    def init(self, key):
        return {"scale": jnp.ones((self.n_ch,)), "bias": jnp.zeros((self.n_ch,))}

    def init_state(self):
        return {"mean": jnp.zeros((self.n_ch,)), "var": jnp.ones((self.n_ch,))}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        axes = tuple(range(x.ndim - 1))
        if train:
            x32 = x.astype(jnp.float32)
            mean = jnp.mean(x32, axes)
            var = jnp.var(x32, axes)
            m = self.momentum
            new_state = {"mean": m * state["mean"] + (1 - m) * mean,
                         "var": m * state["var"] + (1 - m) * var}
        else:
            mean, var = state["mean"], state["var"]
            new_state = None
        inv = jax.lax.rsqrt(var + self.eps)
        nd = self.norm_dtype
        if nd is not None and x.dtype == nd:
            # per-channel affine in the activation dtype: y = x·a + b with
            # a = inv·scale, b = bias − mean·inv·scale (both fp32 → nd)
            a = (inv * params["scale"]).astype(nd)
            b = (params["bias"] - mean * inv * params["scale"]).astype(nd)
            return x * a + b, new_state
        y = (x.astype(jnp.float32) - mean) * inv * params["scale"] \
            + params["bias"]
        return y.astype(x.dtype), new_state


class LayerNorm(Layer):
    """Layer normalization over the trailing feature dim (transformer zoo;
    the CNN zoo's normalizer is :class:`BatchNorm`).  Stats in fp32."""

    def __init__(self, dim: int, eps: float = 1e-5, name: str = "ln"):
        self.dim, self.eps = dim, eps
        self.name = name

    def init(self, key):
        return {"scale": jnp.ones((self.dim,)), "bias": jnp.zeros((self.dim,))}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + self.eps)
        return (y * params["scale"] + params["bias"]).astype(x.dtype)


class Embedding(Layer):
    """Token embedding lookup; fp32 table, output cast to compute dtype."""

    def __init__(self, vocab: int, dim: int, w_init=("normal", 0.02),
                 compute_dtype=jnp.bfloat16, name: str = "embed"):
        self.vocab, self.dim = vocab, dim
        self.w_init = w_init
        self.compute_dtype = compute_dtype
        self.name = name

    def init(self, key):
        return {"w": init_weight(key, (self.vocab, self.dim), self.w_init)}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        return params["w"].astype(self.compute_dtype)[x]


def flash_tiles(t: int, window: Optional[int] = None) -> dict:
    """The splash kernel's ``BlockSizes`` for sequences of ``t``: the
    winner of PR 35's sweep on a v5e at T 4096, heads of 128, bfloat16,
    causal (PERF.md section 6 holds the whole table): the forward takes q
    512 rows and k/v 1,024 at a time, 512 to a product; the fused backward
    (scores made once for dq, dk and dv) 1,024 each way.  A shorter ``t``
    gets the largest multiple of 128 under those that divides it.  Under
    a causal ``window`` (PR 37's sweep at T 8192, window 512, 18 heads:
    3.74 ms forward, forward again and backward against 4.33) the
    forward's k/v tile is no longer than the window, so that fewer keys
    outside it are fetched and masked, and the backward takes q 512 rows
    at a time.  Past 8,192 tokens without a window (a v5e sweep at T
    16,384, 32 heads, q and k of 192, v of 128: 113.4 ms forward, forward
    again and backward against 115.9 at the tiles above, 127.9 unfused)
    the fused backward takes q 512 rows and k/v 2,048 at a time, 1,024 to
    a product: dq leaves the kernel in one part a k/v block, half as many
    parts (8 of ``[32, 16384, 192]`` there)."""
    if t % 128:
        raise ValueError(f"attn_impl='flash' needs a sequence length that "
                         f"is a multiple of 128, the kernel's lanes; got {t}")

    def tile(n, cap):
        return max(b for b in range(128, min(n, cap) + 1, 128) if n % b == 0)

    q, kv = tile(t, 512), tile(t, 1024)
    fwd_kv = kv if window is None else tile(t, max(128, min(window, 1024)))
    if t > 8192 and window is None:     # half as many parts of dq
        return dict(block_q=q, block_kv=fwd_kv,
                    block_kv_compute=tile(fwd_kv, 512), block_q_dkv=q,
                    block_kv_dkv=tile(t, 2048), block_kv_dkv_compute=kv,
                    use_fused_bwd_kernel=True)
    return dict(block_q=q, block_kv=fwd_kv,
                block_kv_compute=tile(fwd_kv, 512),
                block_q_dkv=kv if window is None else q, block_kv_dkv=kv,
                block_kv_dkv_compute=kv, use_fused_bwd_kernel=True)


def attend(q, k, v, *, attn_impl: str, causal: bool = True,
           window: Optional[int] = None, scaled: bool = False):
    """[B, H, T, hd] → [B, H, T, hd] softmax attention, by the splash
    kernel (``attn_impl='flash'``) or the XLA einsum chain
    (``'reference'``).  ``window`` (causal only) keeps the keys
    ``0 <= t - s < window``; ``scaled`` says that q already carries the
    ``1 / sqrt(hd)``."""
    assert window is None or causal, "a window is a causal window"
    if attn_impl == "flash":
        from ..jax_compat import splash_attention
        from ..parallel.mesh import WORKER_AXIS
        # the kernel's tiles hold 16-bit operands at the narrowest
        dt = q.dtype if q.dtype.itemsize >= 2 else jnp.bfloat16
        if not scaled:      # the kernel takes no scale
            q = q.astype(jnp.float32) / (q.shape[-1] ** 0.5)
        mask = ("window", window) if window is not None else \
            "causal" if causal else "full"
        return splash_attention(q.astype(dt), k.astype(dt), v.astype(dt),
                                axis_name=WORKER_AXIS, mask=mask,
                                **flash_tiles(q.shape[2], window)).astype(v.dtype)
    from ..ops.ring_attention import attention_reference
    return attention_reference(q, k, v, causal=causal,
                               scale=1.0 if scaled else None, window=window)


class MultiHeadAttention(Layer):
    """Causal multi-head self-attention (transformer zoo).

    QKV/output projections ride the MXU in ``compute_dtype``; the softmax
    attention itself runs through :func:`ops.ring_attention.attention_reference`
    (fp32 accumulation) — the sequence-SHARDED variant of the same math is
    :func:`ops.ring_attention.ring_attention` on a 2-D data×seq mesh.

    ``attn_impl='flash'`` (TPU only): jax's Pallas splash-attention kernel
    (``jax.experimental.pallas.ops.tpu.splash_attention``: tiled online
    softmax in VMEM with 16-bit operands and float32 accumulation and
    statistics, a custom VJP whose backward is one fused kernel, the mask
    known at trace time so that tiles above the diagonal are never fetched
    and only the diagonal's are masked; never materializes the [T, T]
    scores) instead of the XLA einsum chain, through
    :func:`jax_compat.splash_attention` with :func:`flash_tiles`.  Needs
    seq_len a multiple of 128."""

    def __init__(self, dim: int, n_head: int, causal: bool = True,
                 w_init=("normal", 0.02), compute_dtype=jnp.bfloat16,
                 attn_impl: str = "reference", name: str = "attn"):
        assert dim % n_head == 0
        assert attn_impl in ("reference", "flash"), attn_impl
        self.dim, self.n_head, self.causal = dim, n_head, causal
        self.w_init = w_init
        self.compute_dtype = compute_dtype
        self.attn_impl = attn_impl
        self.name = name

    def _attend(self, q, k, v, scaled: bool = False):
        """[B, H, T, hd] → [B, H, T, hd] softmax attention; ``scaled`` says
        that q already carries the ``1 / sqrt(hd)``."""
        return attend(q, k, v, attn_impl=self.attn_impl, causal=self.causal,
                      scaled=scaled)

    def init(self, key):
        ks = jax.random.split(key, 4)
        mk = lambda k: init_weight(k, (self.dim, self.dim), self.w_init)
        return {"wq": mk(ks[0]), "wk": mk(ks[1]), "wv": mk(ks[2]),
                "wo": mk(ks[3])}

    def _proj(self, params, x, name):
        cd = self.compute_dtype
        b, t, _ = x.shape
        h, hd = self.n_head, self.dim // self.n_head
        y = jnp.dot(x.astype(cd), params[name].astype(cd))
        return y.reshape(b, t, h, hd).transpose(0, 2, 1, 3)    # [B,H,T,hd]

    def apply(self, params, x, *, train=False, rng=None, state=None):
        cd = self.compute_dtype
        b, t, d = x.shape
        q = self._proj(params, x, "wq")
        k = self._proj(params, x, "wk")
        v = self._proj(params, x, "wv")
        o = self._attend(q, k, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, d)
        return jnp.dot(o.astype(cd), params["wo"].astype(cd))

    # -- KV-cache decode path (inference; tests pin it against apply) ------

    def apply_prefill(self, params, x):
        """Full causal forward over the prompt buffer that ALSO returns the
        projected K/V as the decode cache: ``(y, (k, v))``,
        k/v ``[B, H, S, hd]``."""
        cd = self.compute_dtype
        b, t, d = x.shape
        q = self._proj(params, x, "wq")
        k = self._proj(params, x, "wk")
        v = self._proj(params, x, "wv")
        o = self._attend(q, k, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, d)
        return jnp.dot(o.astype(cd), params["wo"].astype(cd)), (k, v)

    def apply_decode(self, params, x1, cache, pos):
        """One decode step: ``x1`` is the CURRENT token's activation
        ``[B, 1, D]`` at position ``pos``; the projected K/V are written
        into the cache at ``pos`` and the query attends to positions
        ``≤ pos`` only.  Returns ``(y [B, 1, D], new_cache)``."""
        cd = self.compute_dtype
        b, _, d = x1.shape
        k_cache, v_cache = cache                      # [B, H, S, hd]
        s = k_cache.shape[2]
        q = self._proj(params, x1, "wq")              # [B, H, 1, hd]
        k1 = self._proj(params, x1, "wk")
        v1 = self._proj(params, x1, "wv")
        k_cache = jax.lax.dynamic_update_slice(k_cache, k1, (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v1, (0, 0, pos, 0))
        from ..ops.ring_attention import NEG_INF
        hd = self.dim // self.n_head
        scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            k_cache.astype(jnp.float32)) / (hd ** 0.5)
        mask = jnp.arange(s) <= pos                    # causal over cache
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p,
                       v_cache.astype(jnp.float32)).astype(x1.dtype)
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, d)
        y = jnp.dot(o.astype(cd), params["wo"].astype(cd))
        return y, (k_cache, v_cache)


class RMSNorm(Layer):
    """Root-mean-square normalisation over the trailing dim (Zhang and
    Sennrich 2019): ``x / sqrt(mean(x^2) + eps) * scale``, no mean taken
    off and no bias.  Statistics in float32."""

    def __init__(self, dim: int, eps: float = 1e-6, name: str = "rms"):
        self.dim, self.eps = dim, eps
        self.name = name

    def init(self, key):
        return {"scale": jnp.ones((self.dim,))}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        with jax.named_scope(self.name):
            x32 = x.astype(jnp.float32)
            ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
            y = x32 * jax.lax.rsqrt(ms + self.eps) * params["scale"]
            return y.astype(x.dtype)


def rotary(x, theta: float = 10000.0, scale: float = 1.0):
    """Rotary position embedding (Su et al. 2021) of ``[..., T, hd]`` in
    the half-split form: at position ``t`` the pair ``(x[i], x[i + hd/2])``
    turns by ``t * theta ** (-2i / hd)``.  The turn is made in float32, and
    so is the multiplication by ``scale``."""
    t, hd = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None]  # [T, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:        # no multiplication by one in a lowering without
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class RotaryAttention(MultiHeadAttention):
    """:class:`MultiHeadAttention` with rotary positions on q and k and no
    position table.  Scopes: ``<name>`` around the layer, ``attn_core``
    around scores to weighted sum, whatever ``attn_impl`` computes them."""

    def __init__(self, dim: int, n_head: int, theta: float = 10000.0, **kw):
        super().__init__(dim, n_head, **kw)
        self.theta = theta

    def apply(self, params, x, *, train=False, rng=None, state=None):
        cd = self.compute_dtype
        b, t, d = x.shape
        with jax.named_scope(self.name):
            # the flash kernel takes no scale: q gets it where the turn
            # has it in float32, not as a second rounding of compute_dtype
            fold = self.attn_impl == "flash"
            q = rotary(self._proj(params, x, "wq"), self.theta,
                       (self.dim // self.n_head) ** -0.5 if fold else 1.0)
            k = rotary(self._proj(params, x, "wk"), self.theta)
            v = self._proj(params, x, "wv")
            with jax.named_scope("attn_core"):
                o = self._attend(q, k, v, scaled=fold)
            o = o.transpose(0, 2, 1, 3).reshape(b, t, d)
            return jnp.dot(o.astype(cd), params["wo"].astype(cd))


def yarn_frequencies(rot: int, theta: float, factor: float,
                     original_max: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's ``rot / 2`` rotary frequencies (Peng et al. 2023, as the
    transformers ``yarn`` initialisation computes them): pair ``i`` of a
    rotary size ``rot`` turns by ``f_i = theta ** (-2i / rot)`` where it
    makes more than ``beta_fast`` turns over ``original_max`` positions,
    by ``f_i / factor`` where it makes fewer than ``beta_slow``, and by a
    linear blend of the two between."""
    f = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)

    def pair_of(turns):     # the pair that makes `turns` over original_max
        return rot * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair_of(beta_fast)), 0)
    hi = min(math.ceil(pair_of(beta_slow)), rot - 1)
    keep = 1.0 - np.clip((np.arange(rot // 2) - lo) / max(hi - lo, 1e-3),
                         0.0, 1.0)
    return (f / factor * (1.0 - keep) + f * keep).astype(np.float32)


def rotary_turn(x, freq, factor: float = 1.0, scale: float = 1.0):
    """``[..., T, hd]`` → float32: the first ``2 * len(freq)`` entries of
    each head turned in half-split pairs, ``(x[i], x[i + len(freq)])`` by
    ``t * freq[i]`` at position ``t``, with cos and sin times ``factor``
    (YaRN's attention factor); the rest of the head passed on; all of it
    times ``scale`` (the softmax scale folded into q)."""
    t, r = x.shape[-2], len(freq)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)[None]                  # [T, r]
    cos, sin = jnp.cos(ang) * (factor * scale), \
        jnp.sin(ang) * (factor * scale)
    x = x.astype(jnp.float32)
    x1, x2, rest = x[..., :r], x[..., r:2 * r], x[..., 2 * r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin]
                           + [rest * scale] * (rest.shape[-1] > 0),
                           axis=-1)


class GroupedQueryAttention(Layer):
    """Causal attention with fewer key/value heads than query heads
    (Ainslie et al. 2023), an optional causal window, rotary positions
    over a part of the head, an optional per-head RMSNorm of q and k and
    an optional per-head output gate (head-wise gated attention,
    arXiv:2505.06708): query head ``i`` reads key/value head
    ``i // (n_q / n_kv)``; with ``qk_norm_eps`` each head of q and of k is
    normed over its ``head_dim`` (scales ``q_norm``, ``k_norm``, in
    float32) before the rotary turn; with ``gate`` ``o_i <- sigmoid(x
    W_g)_i * o_i`` before ``W_o``.  No bias.  ``freq`` are the rotary
    frequencies (their number sets how much of the head turns),
    ``rope_factor`` multiplies cos and sin.  The core is :func:`attend`,
    as :class:`MultiHeadAttention`'s; scopes ``<name>`` and ``attn_core``
    as :class:`RotaryAttention`'s."""

    def __init__(self, dim: int, n_q: int, n_kv: int, head_dim: int, freq,
                 rope_factor: float = 1.0, window: Optional[int] = None,
                 qk_norm_eps: Optional[float] = None, gate: bool = True,
                 w_init=("normal", 0.02), compute_dtype=jnp.bfloat16,
                 attn_impl: str = "reference", name: str = "attn"):
        assert n_q % n_kv == 0, (n_q, n_kv)
        assert attn_impl in ("reference", "flash"), attn_impl
        assert 2 * len(freq) <= head_dim, (len(freq), head_dim)
        self.dim, self.n_q, self.n_kv, self.hd = dim, n_q, n_kv, head_dim
        self.freq, self.rope_factor, self.window = freq, rope_factor, window
        self.qk_norm = None if qk_norm_eps is None else (
            RMSNorm(head_dim, qk_norm_eps, name="q_norm"),
            RMSNorm(head_dim, qk_norm_eps, name="k_norm"))
        self.gate = gate
        self.w_init = w_init
        self.compute_dtype = compute_dtype
        self.attn_impl = attn_impl
        self.name = name

    def init(self, key):
        ks = jax.random.split(key, 5)
        d, q, kv = self.dim, self.n_q * self.hd, self.n_kv * self.hd
        mk = lambda k, shape: init_weight(k, shape, self.w_init)  # noqa: E731
        p = {"wq": mk(ks[0], (d, q)), "wk": mk(ks[1], (d, kv)),
             "wv": mk(ks[2], (d, kv)), "wo": mk(ks[4], (q, d))}
        if self.gate:
            p["wg"] = mk(ks[3], (d, self.n_q))
        for norm in self.qk_norm or ():
            p[norm.name] = norm.init(None)
        return p

    def apply(self, params, x, *, train=False, rng=None, state=None):
        cd = self.compute_dtype
        b, t, _ = x.shape
        x = x.astype(cd)

        def heads(name, n):                                 # [B, n, T, hd]
            y = jnp.dot(x, params[name].astype(cd))
            y = y.reshape(b, t, n, self.hd).transpose(0, 2, 1, 3)
            if self.qk_norm is None or name == "wv":
                return y
            norm = self.qk_norm[name == "wk"]
            return norm.apply(params[norm.name], y.astype(jnp.float32))

        with jax.named_scope(self.name):
            fold = self.attn_impl == "flash"    # as RotaryAttention's
            q = rotary_turn(heads("wq", self.n_q), self.freq,
                            self.rope_factor,
                            self.hd ** -0.5 if fold else 1.0).astype(cd)
            k = rotary_turn(heads("wk", self.n_kv), self.freq,
                            self.rope_factor).astype(cd)
            group = self.n_q // self.n_kv
            k = jnp.repeat(k, group, axis=1)
            v = jnp.repeat(heads("wv", self.n_kv), group, axis=1)
            with jax.named_scope("attn_core"):
                o = attend(q, k, v, attn_impl=self.attn_impl,
                           window=self.window, scaled=fold)
            if self.gate:
                gate = jax.nn.sigmoid(jnp.dot(
                    x, params["wg"].astype(cd),
                    preferred_element_type=jnp.float32))    # [B, T, n_q]
                o = o.transpose(0, 2, 1, 3).astype(jnp.float32) \
                    * gate[..., None]
            else:
                o = o.transpose(0, 2, 1, 3)
            return jnp.dot(o.reshape(b, t, -1).astype(cd),
                           params["wo"].astype(cd))


# The widest products of a gated feed-forward and a short convolution, named
# (``jax.ad_checkpoint.checkpoint_name``) on their ``compute_dtype`` result
# as the product leaves it: the gate's and the up projection's, and the
# convolution's input projection ``[B | C | x~]``.  A checkpoint whose policy
# is ``save_only_these_names(*KEPT_PRODUCTS)`` keeps them for the backward
# pass instead of making them again; under a checkpoint without a policy,
# and outside any, the names change nothing.
KEPT_PRODUCTS = ("mlp_gate", "mlp_up", "conv_in_proj")


class ShortConv(Layer):
    """Gated short convolution, the ``conv`` operator of LiquidAI's LFM2
    layers (``transformers``' ``Lfm2ShortConv``):

        [B | C | x~] = x W_in        (W_in [d, 3d], chunked in that order)
        v = B * x~;   z_t = sum_j w_j * v_{t - L + 1 + j}   (j < L = TAPS)
        y = (C * z) W_out

    a causal depthwise convolution of ``TAPS`` taps (the published
    ``conv_L_cache``), nought before the sequence's start; no bias.  The
    projections run in ``compute_dtype``.  The gates and the convolution (the core, :func:`short_conv_core`)
    compute in float32 on the ``compute_dtype`` projection and hand
    ``C * z`` on in ``compute_dtype``: the core reads three and writes one
    ``[T, d]`` array of ``compute_dtype`` forward, and reads four and
    writes three backward.  Scopes: ``<name>`` around the operator,
    ``conv_core`` around the gates and the convolution alone.  The input
    projection is named ``conv_in_proj`` (:data:`KEPT_PRODUCTS`)."""

    TAPS = 3

    def __init__(self, dim: int, w_init=("normal", 0.02),
                 compute_dtype=jnp.bfloat16, name: str = "conv"):
        self.dim = dim
        self.w_init = w_init
        self.compute_dtype = compute_dtype
        self.name = name

    def init(self, key):
        ki, kc, ko = jax.random.split(key, 3)
        d = self.dim
        return {"in_proj": init_weight(ki, (d, 3 * d), self.w_init),
                "conv": init_weight(kc, (self.TAPS, d), self.w_init),
                "out_proj": init_weight(ko, (d, d), self.w_init)}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        cd = self.compute_dtype
        with jax.named_scope(self.name):
            bcx = checkpoint_name(jnp.dot(x.astype(cd),
                                          params["in_proj"].astype(cd)),
                                  "conv_in_proj")
            with jax.named_scope("conv_core"):
                y = short_conv_core(bcx, params["conv"])
            return jnp.dot(y, params["out_proj"].astype(cd))

    def kept_bytes(self, tokens: int) -> int:
        return tokens * 3 * self.dim * jnp.dtype(self.compute_dtype).itemsize


def short_conv_core(bcx, taps):
    """``[..., T, 3d]`` (B, C and x~ in that order) and ``taps [L, d]`` ->
    ``C * z`` ``[..., T, d]`` in ``bcx``'s dtype, computed in float32: the
    causal convolution as ``L`` shifted multiply-adds over ``v = B * x~``
    padded with ``L - 1`` noughts in front."""
    gate_b, gate_c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    return (gate_c * causal_taps(gate_b * x, taps)).astype(bcx.dtype)


def causal_taps(v, taps):
    """``v [..., T, d]``, ``taps [L, d]`` -> ``z_t = sum_j taps_j *
    v_{t - L + 1 + j}``: a causal depthwise convolution as ``L`` shifted
    multiply-adds over ``v`` padded with ``L - 1`` noughts in front."""
    n, t = taps.shape[0], v.shape[-2]
    pad = [(0, 0)] * v.ndim
    pad[-2] = (n - 1, 0)
    v = jnp.pad(v, pad)
    z = taps[0] * v[..., :t, :]
    for j in range(1, n):
        z = z + taps[j] * v[..., j:j + t, :]
    return z


class GatedMLP(Layer):
    """Gated feed-forward (Shazeer 2020): ``W_d(act(W_g x) * W_u x)``, no
    bias; ``silu`` makes it SwiGLU.  The gate's product in float32.  The
    gate's and the up projection's products are named ``mlp_gate`` and
    ``mlp_up`` (:data:`KEPT_PRODUCTS`)."""

    def __init__(self, dim: int, hidden: int, activation: str = "silu",
                 w_init=("normal", 0.02), compute_dtype=jnp.bfloat16,
                 name: str = "mlp"):
        self.dim, self.hidden = dim, hidden
        self.activation = activation
        self.w_init = w_init
        self.compute_dtype = compute_dtype
        self.name = name

    def init(self, key):
        kg, ku, kd = jax.random.split(key, 3)
        return {"wg": init_weight(kg, (self.dim, self.hidden), self.w_init),
                "wu": init_weight(ku, (self.dim, self.hidden), self.w_init),
                "wd": init_weight(kd, (self.hidden, self.dim), self.w_init)}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        cd = self.compute_dtype
        with jax.named_scope(self.name):
            x = x.astype(cd)
            g = checkpoint_name(jnp.dot(x, params["wg"].astype(cd)),
                                "mlp_gate").astype(jnp.float32)
            u = checkpoint_name(jnp.dot(x, params["wu"].astype(cd)),
                                "mlp_up").astype(jnp.float32)
            h = (_activate(g, self.activation) * u).astype(cd)
            return jnp.dot(h, params["wd"].astype(cd))

    def kept_bytes(self, tokens: int) -> int:
        return 2 * tokens * self.hidden \
            * jnp.dtype(self.compute_dtype).itemsize


class DeltaAttention(Layer):
    """Kimi Delta Attention (KDA, arXiv:2510.26692): a gated delta rule
    whose decay is per channel, the ``kda`` operator of Moonshot's Kimi
    Linear layers.  Per head of ``head_dim`` (``x`` the normed input):

        q, k, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x W_v))
        q <- q / |q| * head_dim^-1/2;   k <- k / |k|
        a_t = -exp(A_log) * softplus(x W_fa W_fb + b_dt)   (per channel)
        beta_t = sigmoid(x W_beta)                          (per head)
        S_t = (I - beta_t k_t k_t^T) Diag(exp a_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
        y = (RMSNorm(o) * w * sigmoid(x W_ga W_gb)) W_o

    ``conv`` is a causal depthwise convolution of ``TAPS`` taps a channel
    (:func:`causal_taps`; the published ``short_conv_kernel_size``), nought
    before the sequence's start; ``S_0 = 0``.  The projections run in
    ``compute_dtype``; the convolutions, norms, decays and gates in
    float32.  The recurrence is :func:`kda_core`, in chunks of ``CHUNK``.
    Scopes: ``<name>`` around the operator, ``kda_core`` around the
    recurrence alone.  No bias but ``b_dt``."""

    TAPS = 4
    CHUNK = 64

    def __init__(self, dim: int, n_head: int, head_dim: int,
                 eps: float = 1e-5, w_init=("normal", 0.02),
                 compute_dtype=jnp.bfloat16, name: str = "kda"):
        self.dim, self.n_head, self.hd = dim, n_head, head_dim
        self.eps = eps
        self.w_init = w_init
        self.compute_dtype = compute_dtype
        self.name = name

    def init(self, key):
        ks = jax.random.split(key, 14)
        d, hd, w = self.dim, self.hd, self.n_head * self.hd
        mk = lambda k, shape: init_weight(k, shape, self.w_init)  # noqa: E731
        # decay rates exp(A_log) in [1, 16] a head; softplus(b_dt) in
        # [1e-3, 1e-1] a channel, log-uniform (the lineage's initialisation)
        dt = jnp.exp(jax.random.uniform(ks[12], (w,), minval=math.log(1e-3),
                                        maxval=math.log(1e-1)))
        return {"wq": mk(ks[0], (d, w)), "wk": mk(ks[1], (d, w)),
                "wv": mk(ks[2], (d, w)),
                **{f"{n}_conv": mk(k, (self.TAPS, w))
                   for n, k in zip("qkv", ks[3:6])},
                "w_fa": mk(ks[6], (d, hd)), "w_fb": mk(ks[7], (hd, w)),
                "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    ks[13], (self.n_head,), minval=1.0, maxval=16.0)),
                "w_beta": mk(ks[8], (d, self.n_head)),
                "w_ga": mk(ks[9], (d, hd)), "w_gb": mk(ks[10], (hd, w)),
                "o_norm": {"scale": jnp.ones((hd,))},
                "wo": mk(ks[11], (w, d))}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        cd, f32 = self.compute_dtype, jnp.float32
        b, t, _ = x.shape
        h, hd = self.n_head, self.hd
        x = x.astype(cd)

        def dot(a, w):
            return jnp.dot(a.astype(cd), params[w].astype(cd))

        # the float32 arithmetic between a projection and the core is made
        # again in the backward pass from the compute_dtype projection
        @functools.partial(jax.checkpoint, static_argnums=(2,))
        def conv(y, taps, scale):                           # [B, T, H, hd]
            y = jax.nn.silu(causal_taps(y.astype(f32), taps)).reshape(
                b, t, h, hd)
            if scale:                   # to the unit sphere, times scale
                y = y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True)
                                      + 1e-6) * scale
            return y.astype(cd)

        with jax.named_scope(self.name):
            q, k, v = (conv(dot(x, "w" + n), params[n + "_conv"], scale)
                       for n, scale in (("q", hd ** -0.5), ("k", 1.0),
                                        ("v", None)))
            a = -jnp.exp(params["A_log"])[:, None] * jax.nn.softplus(
                dot(dot(x, "w_fa"), "w_fb").astype(f32) + params["b_dt"]
            ).reshape(b, t, h, hd)
            beta = jax.nn.sigmoid(dot(x, "w_beta").astype(f32))
            with jax.named_scope("kda_core"):
                o = kda_core(q, k, v, a, beta, chunk=min(self.CHUNK, t),
                             compute_dtype=cd)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                  + self.eps) * params["o_norm"]["scale"]
            gate = jax.nn.sigmoid(dot(dot(x, "w_ga"), "w_gb").astype(f32))
            o = o.reshape(b, t, h * hd) * gate
            return jnp.dot(o.astype(cd), params["wo"].astype(cd))


def kda_core(q, k, v, a, beta, *, chunk: int, compute_dtype):
    """The KDA recurrence of :class:`DeltaAttention` over ``q, k [B, T, H,
    dk]``, ``v [B, T, H, dv]``, the log decay ``a [B, T, H, dk]`` (<= 0)
    and ``beta [B, T, H]`` -> ``o [B, T, H, dv]`` float32, in the chunked
    WY form of the gated delta rule (arXiv:2412.06464, the per-channel
    decay of arXiv:2510.26692).  Inside a chunk of ``chunk`` tokens, with
    ``G`` the cumulative log decay from its start and ``S`` the state it
    starts from:

        A_ij = sum_c q_ic k_jc e^{G_ic - G_jc}  (j <= i),  L_ij = beta_i
        sum_c k_ic k_jc e^{G_ic - G_jc}  (j < i),  T = (I + L)^-1
        W = T (beta K e^G),  U = T (beta V) - W S
        O = (Q e^G) S + A U,  S' = e^{G_last} S + (K e^{G_last - G})^T U

    Every exponent formed is a difference that cannot be positive (``G``
    falls): ``G_i - G_j`` for ``i >= j`` (:func:`_decayed_pairs`) or
    ``G_last - G_i``, so no decay, however strong, overflows.  ``T`` and
    the decays are float32; the pairs' products, ``T``'s applications and
    the carries' products take ``compute_dtype`` operands and sum in
    float32.

    The chunks go in groups of ``KDA_GROUP``: for each group what needs no
    state (:func:`_kda_chunk_work`) for all its chunks at once, then a
    ``lax.scan`` that carries ``S`` alone from chunk to chunk, two products
    a step, then the group's outputs from the states it passed.  The
    gradient is written out (:func:`_kda_bwd`): the groups in reverse, a
    ``lax.scan`` carrying ``dS`` alone, two products a step, and the chunk
    work's own gradient written out too (:func:`_kda_chunk_back`); the
    backward pass keeps the state each chunk starts from, float32, and of
    the chunk work ``T``, ``a_kk`` and ``a_qk`` (:func:`_kda_pack`), so
    that it makes nothing of the pairs or of ``T`` again."""
    f32 = jnp.float32
    b, t, h, _ = q.shape
    c = chunk
    n = t // c
    assert n * c == t and c & (c - 1) == 0, (t, c)
    per = math.gcd(n, KDA_GROUP)

    def groups(x):              # [B, T, H, ...] -> [N / per, per, B, H, C, ...]
        x = x.reshape(b, n, c, h, *x.shape[3:])
        x = x.transpose(1, 0, 3, 2, *range(4, x.ndim))
        return x.reshape(n // per, per, *x.shape[1:])

    o = _kda(compute_dtype, *(groups(x) for x in (
        q, k, v, a.astype(f32), beta[..., None].astype(f32))))
    return o.reshape(n, b, h, c, -1).transpose(1, 0, 3, 2, 4).reshape(
        b, t, h, -1)


# chunks whose state-free work is made at once: what the recurrence holds of
# it at a time is one group's
KDA_GROUP = 16


def _mm(eq, x, y):
    return jnp.einsum(eq, x, y, preferred_element_type=jnp.float32)


def _kda_chunk_work(cd, q, k, v, a, beta):
    """``(w, u, qg, kg, a_qk, fall)`` of :func:`kda_core` for chunks ``[...,
    C, d]`` (``beta`` ``[..., C, 1]``; ``fall`` ``[..., dk, 1]``): what a
    chunk needs that does not depend on the state it starts from; and
    ``(T, a_kk)`` (float32), what its pull-back (:func:`_kda_chunk_back`)
    reads instead of making them again."""
    f32 = jnp.float32
    c = q.shape[-2]
    q, k, v = (y.astype(f32) for y in (q, k, v))
    g = jnp.cumsum(a, axis=-2)
    a_qk, a_kk = _decayed_pairs(jnp.stack([q, k]), k, g, cd)
    a_qk = a_qk + jnp.sum(q * k, -1)[..., None] * jnp.eye(c, dtype=f32)
    t = _unit_lower_inverse(beta * a_kk)
    w, u, qg, kg, fall = _kda_from_inverse(cd, t, q, k, v, g, beta)
    return (w, u, qg, kg, a_qk.astype(cd), fall), (t, a_kk)


def _kda_from_inverse(cd, t, q, k, v, g, beta):
    """``(w, u, qg, kg, fall)`` of :func:`_kda_chunk_work` from ``T`` and
    ``G``, the cumulative log decay (``q, k, v`` float32)."""
    inv = t.astype(cd)
    w = _mm("...ij,...jd->...id", inv, (beta * k * jnp.exp(g)).astype(cd))
    u = _mm("...ij,...jd->...id", inv, (beta * v).astype(cd))
    g_last = g[..., -1:, :]
    qg, kg = q * jnp.exp(g), k * jnp.exp(g_last - g)
    fall = jnp.exp(g_last).swapaxes(-1, -2)
    return w.astype(cd), u, qg.astype(cd), kg.astype(cd), fall


def _kda_chunk_back(cd, q, k, v, a, beta, t, a_kk, cots):
    """The cotangents of :func:`_kda_chunk_work`'s inputs from those of
    its first six outputs, ``cots``, written out with ``T`` and ``a_kk`` as
    the forward made them: ``dT = dW (beta K e^G)^T + dU (beta V)^T``;
    ``T = (I + L)^-1`` gives ``dL = -T^T dT T^T`` on the strictly lower
    triangle, two products at the highest precision where autodiff
    transposes every level of :func:`_unit_lower_inverse`; the pairs by
    :func:`_decayed_pairs_back`; ``dG`` summed back to ``da`` in reverse.
    Products take ``compute_dtype`` operands as the forward's do."""
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    dw, du, dqg, dkg, da_qk, dfall = (x.astype(f32) for x in cots)
    c = q.shape[-2]
    dtypes = [y.dtype for y in (q, k, v)]
    q, k, v = (y.astype(f32) for y in (q, k, v))
    g = jnp.cumsum(a, axis=-2)
    eg, g_last = jnp.exp(g), g[..., -1:, :]
    ekl = jnp.exp(g_last - g)
    kb = beta * k * eg
    inv, dw, du = t.astype(cd), dw.astype(cd), du.astype(cd)
    dinv = (_mm("...id,...jd->...ij", dw, kb.astype(cd))
            + _mm("...id,...jd->...ij", du, (beta * v).astype(cd)))
    dkb = _mm("...ji,...jd->...id", inv, dw)
    dvb = _mm("...ji,...jd->...id", inv, du)
    dl = jnp.where(np.tri(c, k=-1, dtype=bool), -jnp.einsum(
        "...ji,...jk,...lk->...il", t, dinv, t, precision=hi), 0.0)
    # the diagonal q.k of a_qk, then the pairs below it
    diag = jnp.diagonal(da_qk, axis1=-2, axis2=-1)[..., None]
    (dq, dk), dcol, dg = _decayed_pairs_back(
        jnp.stack([q, k]), k, g, cd, jnp.stack([da_qk, beta * dl]))
    dq = dq + diag * k + dqg * eg
    dk = dk + dcol + diag * q + dkb * beta * eg + dkg * ekl
    dv = dvb * beta
    dbeta = (jnp.sum(dl * a_kk, -1, keepdims=True)
             + jnp.sum(dkb * k * eg + dvb * v, -1, keepdims=True))
    dkl = dkg * k * ekl
    dg = dg + dkb * kb + dqg * q * eg - dkl
    dg = dg.at[..., -1:, :].add(jnp.sum(dkl, -2, keepdims=True)
                                + dfall.swapaxes(-1, -2) * jnp.exp(g_last))
    da = jax.lax.cumsum(dg, axis=dg.ndim - 2, reverse=True)
    return (dq.astype(dtypes[0]), dk.astype(dtypes[1]), dv.astype(dtypes[2]),
            da, dbeta)


def _varying_zeros(shape, like):
    """Float32 zeros of ``like``'s ``vma`` type in a ``shard_map``: a
    scan's first carry."""
    z = jnp.zeros(shape, jnp.float32)
    for axis in jax.typeof(like).vma:
        z = vary(z, axis)
    return z


def _kda_pack(t, a_kk, a_qk):
    """``T^T`` (unit upper: the backward pass takes ``T`` turned) and
    ``a_kk`` (strictly lower) as one float32 ``[..., C, C]``, and ``a_qk``,
    each with 128 entries a row where ``C^2`` allows: a TPU tile holds 128
    in its minor dimension, and ``[C, C]`` of 64 would fill half of it."""
    packed = jnp.where(np.tri(t.shape[-1], k=-1, dtype=bool), a_kk,
                       t.swapaxes(-1, -2))

    def lanes(x):
        c2 = x.shape[-1] * x.shape[-2]
        return x.reshape(*x.shape[:-2], c2 // 128, 128) if c2 % 128 == 0 \
            else x
    return lanes(packed), lanes(a_qk)


def _kda_unpack(kept, c):
    """``(T, a_kk, a_qk)`` of ``C x C`` from :func:`_kda_pack`'s form."""
    packed, a_qk = (x.reshape(*x.shape[:-2], c, c) for x in kept)
    below = np.tri(c, k=-1, dtype=bool)
    return (jnp.where(below, 0.0, packed).swapaxes(-1, -2),
            jnp.where(below, packed, 0.0), a_qk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kda(cd, q, k, v, a, beta):
    return _kda_fwd(cd, q, k, v, a, beta)[0]


def _kda_fwd(cd, *ins):
    """``O`` of grouped chunks ``[N / per, per, B, H, C, ...]``, and what
    the backward pass keeps: the inputs, the state each chunk starts from,
    ``D = U - W S`` as the carry used it, and of the chunk work ``T``,
    ``a_kk`` (float32) and ``a_qk`` (``compute_dtype``)."""
    q, v = ins[0], ins[2]

    def carry(s, xs):
        w, u, kg, fall = xs
        d = (u - _mm("...id,...de->...ie", w, s.astype(cd))).astype(cd)
        return fall * s + _mm("...id,...ie->...de", kg, d), (s, d)

    def group(s, xs):
        (w, u, qg, kg, a_qk, fall), (t, a_kk) = _kda_chunk_work(cd, *xs)
        s, (ss, d) = jax.lax.scan(carry, s, (w, u, kg, fall))
        return s, (_mm("...id,...de->...ie", qg, ss.astype(cd))
                   + _mm("...ij,...je->...ie", a_qk, d), ss, d,
                   _kda_pack(t, a_kk, a_qk))

    _, (o, s, d, kept) = jax.lax.scan(group, _varying_zeros(
        (*q.shape[2:4], q.shape[-1], v.shape[-1]), q), ins)
    return o, (ins, s, d, kept)


def _kda_bwd(cd, res, do):
    """The inputs' cotangents from ``O``'s, the groups in reverse: ``W``
    made again from the kept ``T``, ``Q e^G``, ``K e^{G_last - G}`` and
    the last decay from ``G``; a ``lax.scan`` carrying ``dS`` alone, two
    products a step (``dD = A^T dO + K' dS'`` as one product over ``[dO |
    dS']``, ``dS = e^G_last dS' + [Q e^G | -W]^T [dO | dD]`` as another),
    that passes ``dS'`` (of the state the chunk hands on), ``dD`` and the
    last decay's cotangent; then the chunk work's cotangents for the
    group's chunks at once, pulled back by :func:`_kda_chunk_back`."""
    ins, s, d, kept = res
    f32 = jnp.float32
    cat = functools.partial(jnp.concatenate, axis=-2)

    def carry(ds, xs):
        ak, qw, fall, s, do = xs
        dd = _mm("...ji,...je->...ie", ak, cat([do, ds.astype(cd)]))
        return fall * ds + _mm("...id,...ie->...de", qw, cat(
            [do, dd.astype(cd)])), (ds.astype(cd), dd, jnp.sum(
                s * ds, -1, keepdims=True))

    def group(ds, xs):
        ins, s, d, kept, do = xs
        t, a_kk, a_qk = _kda_unpack(kept, do.shape[-2])
        q, k, v, a, beta = ins
        w, _, qg, kg, fall = _kda_from_inverse(
            cd, t, *(y.astype(f32) for y in (q, k, v)),
            jnp.cumsum(a, axis=-2), beta)
        ds, (ds_out, dd, dfall) = jax.lax.scan(carry, ds, (
            cat([a_qk, kg.swapaxes(-1, -2)]), cat([qg, -w]), fall, s, do),
            reverse=True)
        s, dd_cd = s.astype(cd), dd.astype(cd)
        c = jnp.arange(do.shape[-2])
        cots = (-_mm("...ie,...de->...id", dd_cd, s),               # W
                dd,                                                 # U
                _mm("...ie,...de->...id", do, s),                   # Q e^G
                _mm("...ie,...de->...id", d, ds_out),               # K e^..
                jnp.where(c[:, None] >= c, _mm("...ie,...je->...ij", do,
                                               d), 0.0),            # A
                dfall)                                              # e^G_l
        return ds, _kda_chunk_back(cd, *ins, t, a_kk, cots)

    return jax.lax.scan(group, _varying_zeros(s.shape[2:], s),
                        (ins, s, d, kept, do.astype(cd)), reverse=True)[1]


_kda.defvjp(_kda_fwd, _kda_bwd)


def _parting(c: int):
    """``(b, mask)`` for each level of the pairs ``j < i`` of ``C``
    positions (a power of two): the pair first parts where blocks of
    ``2b`` split into halves of ``b``, ``i`` in the right half and ``j``
    in the left; the ``[C, C]`` masks of the levels add up to the strictly
    lower triangle."""
    i = np.arange(c)
    out, b = [], 1
    while b < c:
        same = (i[:, None] // (2 * b)) == (i[None, :] // (2 * b))
        out.append((b, same & ((i[:, None] // b) % 2 == 1)
                    & ((i[None, :] // b) % 2 == 0)))
        b *= 2
    return out


def _level_decays(g, b):
    """``(e^{g_i - g_r}, e^{g_r - g_j})`` of :func:`_decayed_pairs`' level
    of halves ``b``, ``r`` the last position of each left half: the first
    scales the right halves' rows, the second the left halves' columns,
    and each is 1 on the side it does not scale."""
    *lead, c, d = g.shape
    halves = g.reshape(*lead, c // (2 * b), 2 * b, d)
    ref = jnp.broadcast_to(halves[..., b - 1:b, :],
                           halves.shape).reshape(g.shape)
    right = ((np.arange(c) // b) % 2 == 1)[:, None]
    return (jnp.exp(jnp.where(right, g - ref, 0.0)),
            jnp.exp(jnp.where(right, 0.0, ref - g)))


def _decayed_pairs(x, col, g, compute_dtype):
    """``[..., C, C]``, strictly lower: ``sum_c x_ic col_jc e^{g_ic -
    g_jc}`` for ``j < i``, where ``g`` falls along ``C`` (``x``, ``col``,
    ``g`` ``[..., C, d]``; ``x`` may hold more rows ahead, ``[S, ..., C,
    d]``).  At each level of :func:`_parting` one product of the rows times
    ``e^{g_i - g_r}`` and the columns times ``e^{g_r - g_j}``, ``r`` the
    last position of the left half, kept where the level's mask is: both
    exponents are differences that are not positive there (a row of the
    left half and a column of the right half take nought, and are
    masked)."""
    f32 = jnp.float32
    out = 0.0
    for b, mask in _parting(col.shape[-2]):
        er, ec = _level_decays(g, b)
        rows, cols = x * er, col * ec
        out = out + jnp.where(mask, jnp.einsum(
            "...id,...jd->...ij", rows.astype(compute_dtype),
            cols.astype(compute_dtype), preferred_element_type=f32), 0.0)
    return out


def _decayed_pairs_back(x, col, g, compute_dtype, dout):
    """The cotangents of :func:`_decayed_pairs`' ``x``, ``col`` and ``g``
    from its output's ``dout``, for ``x`` of one axis more than ``col``
    (``[S, ..., C, d]``, ``dout`` ``[S, ..., C, C]``): at each level two
    products, the rows' and the columns' (the latter summed over ``S`` in
    the product).  A pair depends on ``g`` through ``g_i - g_j`` alone (the
    reference cancels), so ``dg = sum_S x dx - col dcol``."""
    dx = dcol = 0.0
    for b, mask in _parting(col.shape[-2]):
        er, ec = _level_decays(g, b)
        dp = jnp.where(mask, dout, 0.0).astype(compute_dtype)
        dx = dx + er * _mm("...jd,s...ij->s...id",
                           (col * ec).astype(compute_dtype), dp)
        dcol = dcol + ec * _mm("s...ij,s...id->...jd", dp,
                               (x * er).astype(compute_dtype))
    return dx, dcol, jnp.sum(x * dx, 0) - col * dcol


def _unit_lower_inverse(l):
    """``(I + l)^-1`` of a strictly lower ``[..., C, C]`` in float32, by
    block forward substitution doubled level by level (:func:`_parting`):
    with ``X`` the inverse of the diagonal blocks of ``b``, ``X - X (M_b
    l) X`` is that of the blocks of ``2b`` (``[[A, 0], [B, D]]^-1 = [[A^-1,
    0], [-D^-1 B A^-1, D^-1]]``), every product at the highest precision
    and of the whole ``[C, C]``."""
    hi = jax.lax.Precision.HIGHEST
    c = l.shape[-1]
    x = jnp.eye(c, dtype=l.dtype)
    for _, mask in _parting(c):
        x = x - jnp.einsum("...ij,...jk,...kl->...il", x,
                           jnp.where(mask, l, 0.0), x, precision=hi)
    return x


class LatentAttention(Layer):
    """Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434)
    with the query projected whole and no rotary turn (the ``mla``
    operator of Kimi Linear, ``q_lora_rank`` null and ``mla_use_nope``):

        q_h = [q^n_h | q^r_h] = (x W_q)_h
        [c | k^r] = x W_kva;   c <- RMSNorm(c)
        [k^n_h | v_h] = (c W_kvb)_h;   k_h = [k^n_h | k^r]
        o_h = softmax_causal(q_h k_h^T / sqrt(nope + rope)) v_h
        y = concat(o_h) W_o

    ``k^r`` is one ``rope``-wide part shared by every head.  The core is
    :func:`attend` with the scale folded into q in float32; query and key
    heads are ``nope + rope`` wide, value heads ``v_dim``.  No bias.
    Scopes: ``<name>`` around the operator, ``attn_core`` around the core,
    ``kv_norm`` around the latent's norm."""

    def __init__(self, dim: int, n_head: int, latent: int, nope: int,
                 rope: int, v_dim: int, eps: float = 1e-5,
                 w_init=("normal", 0.02), compute_dtype=jnp.bfloat16,
                 attn_impl: str = "reference", name: str = "mla"):
        assert attn_impl in ("reference", "flash"), attn_impl
        self.dim, self.n_head, self.latent = dim, n_head, latent
        self.nope, self.rope, self.v_dim = nope, rope, v_dim
        self.kv_norm = RMSNorm(latent, eps, name="kv_norm")
        self.w_init = w_init
        self.compute_dtype = compute_dtype
        self.attn_impl = attn_impl
        self.name = name

    def init(self, key):
        ks = jax.random.split(key, 4)
        d, h, qk = self.dim, self.n_head, self.nope + self.rope
        mk = lambda k, shape: init_weight(k, shape, self.w_init)  # noqa: E731
        return {"wq": mk(ks[0], (d, h * qk)),
                "w_kva": mk(ks[1], (d, self.latent + self.rope)),
                "kv_norm": self.kv_norm.init(None),
                "w_kvb": mk(ks[2], (self.latent, h * (self.nope + self.v_dim))),
                "wo": mk(ks[3], (h * self.v_dim, d))}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        cd, f32 = self.compute_dtype, jnp.float32
        b, t, _ = x.shape
        h, qk = self.n_head, self.nope + self.rope
        x = x.astype(cd)

        def dot(a, w):
            return jnp.dot(a.astype(cd), params[w].astype(cd),
                           preferred_element_type=f32)

        with jax.named_scope(self.name):
            q = dot(x, "wq").reshape(b, t, h, qk) * qk ** -0.5
            ckr = dot(x, "w_kva")
            c = self.kv_norm.apply(params["kv_norm"], ckr[..., :self.latent])
            kv = dot(c, "w_kvb").reshape(b, t, h, self.nope + self.v_dim)
            k = jnp.concatenate([kv[..., :self.nope], jnp.broadcast_to(
                ckr[:, :, None, self.latent:], (b, t, h, self.rope))], -1)
            heads = [y.astype(cd).transpose(0, 2, 1, 3)             # [B, H,
                     for y in (q, k, kv[..., self.nope:])]          # T, hd]
            with jax.named_scope("attn_core"):
                o = attend(*heads, attn_impl=self.attn_impl, scaled=True)
            o = o.transpose(0, 2, 1, 3).reshape(b, t, h * self.v_dim)
            return jnp.dot(o.astype(cd), params["wo"].astype(cd))


class Flatten(Layer):
    def __init__(self, name: str = "flatten"):
        self.name = name

    def apply(self, params, x, *, train=False, rng=None, state=None):
        return x.reshape((x.shape[0], -1))


class Reshape(Layer):
    """Reshape trailing dims to ``shape`` (batch dim preserved)."""

    def __init__(self, shape: Tuple[int, ...], name: str = "reshape"):
        self.shape = tuple(shape)
        self.name = name

    def apply(self, params, x, *, train=False, rng=None, state=None):
        return x.reshape((x.shape[0],) + self.shape)


class Activation(Layer):
    def __init__(self, kind: str = "relu", name: str = "act"):
        self.kind = kind
        self.name = name

    def apply(self, params, x, *, train=False, rng=None, state=None):
        return _activate(x, self.kind)


def _activate(x, kind: Optional[str]):
    if kind is None or kind == "linear":
        return x
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "tanh":
        return jnp.tanh(x)
    if kind == "sigmoid":
        return jax.nn.sigmoid(x)
    if kind == "leaky_relu":
        return jax.nn.leaky_relu(x, 0.2)
    if kind == "silu":
        return jax.nn.silu(x)
    raise ValueError(f"unknown activation {kind!r}")


# ---------------------------------------------------------------------------
# Loss / error heads (reference: layers2.Softmax negative_log_likelihood +
# errors / errors_top_x)
# ---------------------------------------------------------------------------

def softmax_cross_entropy(logits, labels,
                          label_smoothing: float = 0.0) -> jnp.ndarray:
    """Mean NLL of integer ``labels`` under softmax(logits), in float32.

    ``label_smoothing=ε`` mixes the one-hot target with the uniform
    distribution (Szegedy et al. 2016): loss = (1−ε)·NLL + ε·mean_k(−log
    p_k) — exact, not the folded approximation."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    nll = jnp.mean(logz - ll)
    if label_smoothing:
        eps = float(label_smoothing)
        uniform = logz - jnp.mean(logits, axis=-1)            # −mean log p_k
        return (1.0 - eps) * nll + eps * jnp.mean(uniform)
    return nll


# -- a whole-vocabulary head: loss and gradients from one pass ---------------
# Reverse mode cannot form ``softmax - onehot`` before each token's
# cotangent arrives, so it keeps the logits or makes them again.  Where the
# per-token weights ``c`` are known before the head runs, the head is
# ``S = sum_t c_t * ce_t``, linear in ``c``: a block makes its logits once
# and from them its loss, the gradient to its states and its share of the
# gradient to the weight, and the backward rule is three scalings.

def _head_blocks(block: int, *per_token):
    """Each ``[M, ...]`` array as ``[M / blk, blk, ...]``, ``blk`` the
    block size or all ``M`` tokens where they are fewer."""
    m = per_token[0].shape[0]
    blk = min(block, m)
    assert m % blk == 0, (
        f"{m} tokens a step do not divide into blocks of {blk}")
    return tuple(a.reshape((m // blk, blk) + a.shape[1:]) for a in per_token)


def head_logit_products(tokens: int, block: int) -> int:
    """``[block, V]``-sized products one differentiated
    :func:`weighted_cross_entropy` over ``tokens`` tokens makes: a block's
    logits, its gradient to the states and its share of the gradient to
    the weight (reverse mode over a rematerialised block makes four)."""
    return 3 * (tokens // min(block, tokens))


def _block_logits(w_cd, h, y):
    """One block's float32 logits, their row maxima and sums of
    exponentials, each token's cross-entropy and its top-1 miss."""
    logits = jnp.dot(h.astype(w_cd.dtype), w_cd,
                     preferred_element_type=jnp.float32)
    top = jnp.max(logits, axis=-1, keepdims=True)
    z = jnp.sum(jnp.exp(logits - top), axis=-1, keepdims=True)
    ce = (top + jnp.log(z) - jnp.take_along_axis(
        logits, y[:, None], axis=-1))[:, 0]
    miss = (jnp.argmax(logits, axis=-1) != y).astype(jnp.float32)
    return (logits, top, z), ce, miss


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _weighted_ce(w, h, y, c, block, cd):
    w_cd = w.astype(cd)

    def one(_, hy):
        return None, _block_logits(w_cd, *hy)[1:]

    _, (ce, miss) = jax.lax.scan(one, None, _head_blocks(block, h, y))
    ce = ce.reshape(-1)
    return jnp.sum(c * ce), ce, miss.reshape(-1)


def _weighted_ce_fwd(w, h, y, c, block, cd):
    w_cd = w.astype(cd)

    def one(dw, hyc):
        h, y, c = hyc
        (logits, top, z), ce, miss = _block_logits(w_cd, h, y)
        hot = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) \
            == y[:, None]
        # written once: left to itself the compiler makes it again inside
        # each of the two products that read it (145 against 159 ms over
        # the looped cell's 16 blocks on a v5e)
        dlogits = jax.lax.optimization_barrier(
            (c[:, None] * (jnp.exp(logits - top) / z - hot)).astype(cd))
        dh = jnp.dot(dlogits, w_cd.T,
                     preferred_element_type=jnp.float32).astype(cd)
        dw = dw + jax.lax.dot_general(
            h.astype(cd), dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dw, (ce, miss, dh)

    dw, (ce, miss, dh) = jax.lax.scan(
        one, jax.lax.full_like(w, 0, jnp.float32),
        _head_blocks(block, h, y, c))
    ce = ce.reshape(-1)
    return (jnp.sum(c * ce), ce, miss.reshape(-1)), \
        (dw, dh.reshape(h.shape), ce)


def _weighted_ce_bwd(block, cd, res, cts):
    dw, dh, ce = res
    g = cts[0]                  # ce and miss are reports: no gradient
    return g * dw, g * dh.astype(jnp.float32), None, g * ce


_weighted_ce.defvjp(_weighted_ce_fwd, _weighted_ce_bwd)


def weighted_cross_entropy(w, h, y, c, *, block: int, compute_dtype):
    """``(S, ce [M], miss [M])`` of a head ``w [d, V]`` over ``M`` tokens:
    states ``h [M, d]``, targets ``y [M]``, given weights ``c [M]``
    (``w``, ``h``, ``c`` float32); ``S = sum_t c_t * ce_t``, each token's
    cross-entropy and top-1 miss beside it for reporting, carrying no
    gradient.  ``block`` tokens' logits exist at once: operands in
    ``compute_dtype``, float32 accumulation, statistics and loss.

    Differentiated, one scan over the blocks yields ``S`` and its
    gradients: a block makes its logits once, ``c * (softmax - onehot)``
    in ``compute_dtype``, from it the gradient to its states (kept in
    ``compute_dtype``) and its share of the gradient to ``w`` (summed in
    float32); the backward rule scales them by the cotangent of ``S``
    (``c``'s gradient is ``ce``).  Not differentiated, it makes the
    logits and the statistics alone."""
    for axis in jax.typeof(h).vma:      # a constant ``c`` inside a shard_map
        c = vary(c, axis)               # gets its gradient per worker too
    return _weighted_ce(w, h, y, c, block, jnp.dtype(compute_dtype))


def errors(logits, labels) -> jnp.ndarray:
    """Top-1 error rate."""
    return jnp.mean((jnp.argmax(logits, axis=-1) != labels).astype(jnp.float32))


def errors_top_x(logits, labels, x: int = 5) -> jnp.ndarray:
    """Top-x error rate (reference reports top-5 for ImageNet).  Clamped to
    the class count so small smoke models can reuse the standard head."""
    x = min(x, logits.shape[-1])
    _, topk = jax.lax.top_k(logits, x)
    hit = jnp.any(topk == labels[:, None], axis=-1)
    return jnp.mean((~hit).astype(jnp.float32))
