"""Decoder-only transformer language model.

Beyond-parity model family (the reference zoo, SURVEY.md §2.7, is CNN-only):
a GPT-style causal LM following the SAME duck-typed model contract as the
CNN zoo, so every rule/exchanger/worker/bench path drives it unchanged —
``rule.init(modelfile='theanompi_tpu.models.transformer_lm',
modelclass='TransformerLM')``.

Attention runs in-model over the full (replicated) sequence; the
sequence-SHARDED path for long contexts is ``ops/ring_attention.py``'s ring
algorithm on a 2-D data×seq mesh (same math, pinned equal in
``tests/test_ring_attention.py``).

Without a data dir it synthesizes a deterministic, genuinely learnable token
stream (noisy modular-increment chains) so convergence smokes run with zero
setup, like the CIFAR-10 synthetic fallback.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from .data import DataBase
from .model_base import ModelBase


class LMData(DataBase):
    """Synthetic next-token-prediction data: x[t+1] = x[t] + 1 (mod V) with
    ``noise`` probability of a random token — learnable one-step rule."""

    def __init__(self, config=None, batch_size=16, seq_len=64, vocab=64,
                 n_train=1024, n_val=256, noise=0.05):
        super().__init__(config, batch_size)
        seq_len = int(self.config.get("seq_len", seq_len))
        vocab = int(self.config.get("vocab", vocab))
        n_train = int(self.config.get("synthetic_train", n_train))
        n_val = int(self.config.get("synthetic_val", n_val))
        noise = float(self.config.get("noise", noise))
        self._train_seq = self._draw(n_train, 101, seq_len, vocab, noise)
        self._val_seq = self._draw(n_val, 202, seq_len, vocab, noise)
        # DataBase bookkeeping keys off x/y arrays
        self.x_train = self._train_seq[:, :-1]
        self.y_train = self._train_seq[:, 1:]
        self.x_val = self._val_seq[:, :-1]
        self.y_val = self._val_seq[:, 1:]
        self._finalize()

    def _draw(self, n, seed, seq_len, vocab, noise):
        """``[n, seq_len + 1]`` int32 ids: inputs and next-token targets."""
        r = np.random.RandomState(seed)
        start = r.randint(0, vocab, (n, 1))
        seq = (start + np.arange(seq_len + 1)) % vocab
        flip = r.rand(n, seq_len + 1) < noise
        seq = np.where(flip, r.randint(0, vocab, seq.shape), seq)
        return seq.astype(np.int32)

    def _make_batch(self, x, y, train):
        # token ids stay int32 (the base class casts images to float32)
        return {"x": np.ascontiguousarray(x, dtype=np.int32),
                "y": np.ascontiguousarray(y, dtype=np.int32)}


class Block(L.Layer):
    """Pre-LN transformer block: LN→MHA→residual, LN→MLP→residual.

    ``tp > 1`` (tensor parallelism, ``parallel/tp.py``): the attention is
    head-sharded and the MLP column→row-parallel over the ``'model'`` mesh
    axis — same init and math as the dense block (pinned equal in
    ``tests/test_tp.py``), two psums per block."""

    has_state = False
    supports_kv_decode = True     # apply_prefill/apply_decode work (dense)

    def __init__(self, dim, n_head, mlp_ratio=4, cd=jnp.bfloat16, tp=1,
                 sp=1, attn_impl="reference", name="block"):
        from ..parallel import tp as tplib
        self.name = name
        self.tp = tp
        self.ln1 = L.LayerNorm(dim, name="ln1")
        if tp > 1 and sp > 1:
            # 3-D data×seq×model: local heads (tp) over local token blocks
            # (sp) — ring attention on the head shard, row-parallel out psum
            assert attn_impl == "reference", (
                f"attn_impl={attn_impl!r} does not apply under sp>1 "
                "(sequence-sharded attention is the ring kernel)")
            from ..parallel.sp import TPRingMultiHeadAttention
            self.attn = TPRingMultiHeadAttention(dim, n_head, tp,
                                                 compute_dtype=cd,
                                                 name="attn")
        elif tp > 1:
            self.attn = tplib.TPMultiHeadAttention(dim, n_head, tp,
                                                   compute_dtype=cd,
                                                   attn_impl=attn_impl,
                                                   name="attn")
        elif sp > 1:
            # sequence-sharded activations: ring attention over 'seq' — the
            # blockwise accumulate is its own kernel, so a flash request
            # must fail fast rather than silently measure the ring path
            assert attn_impl == "reference", (
                f"attn_impl={attn_impl!r} does not apply under sp>1 "
                "(sequence-sharded attention is the ring kernel)")
            from ..parallel.sp import RingMultiHeadAttention
            self.attn = RingMultiHeadAttention(dim, n_head, compute_dtype=cd,
                                               name="attn")
        else:
            self.attn = L.MultiHeadAttention(dim, n_head, compute_dtype=cd,
                                             attn_impl=attn_impl,
                                             name="attn")
        self.ln2 = L.LayerNorm(dim, name="ln2")
        # fc1 is column-parallel under tp: a plain FC applied to the local
        # weight shard IS the column-parallel layer (only the spec differs)
        self.fc1 = L.FC(dim, mlp_ratio * dim, w_init=("normal", 0.02),
                        activation="relu", compute_dtype=cd, name="fc1")
        fc2_cls = tplib.RowFC if tp > 1 else L.FC
        self.fc2 = fc2_cls(mlp_ratio * dim, dim, w_init=("normal", 0.02),
                           activation=None, compute_dtype=cd, name="fc2")

    def specs(self):
        """Per-leaf PartitionSpecs over the 'model' axis (None when dense)."""
        if self.tp == 1:
            return None
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import MODEL_AXIS as M
        ln = {"scale": P(), "bias": P()}
        col = {"w": P(None, M), "b": P(M)}
        return {"ln1": ln, "ln2": ln,
                "attn": {"wq": P(None, M), "wk": P(None, M),
                         "wv": P(None, M), "wo": P(M, None)},
                "fc1": col, "fc2": {"w": P(M, None), "b": P()}}

    def init(self, key):
        ks = jax.random.split(key, 5)
        return {"ln1": self.ln1.init(ks[0]), "attn": self.attn.init(ks[1]),
                "ln2": self.ln2.init(ks[2]), "fc1": self.fc1.init(ks[3]),
                "fc2": self.fc2.init(ks[4])}

    def apply(self, params, x, *, train=False, rng=None, state=None):
        h = self.ln1.apply(params["ln1"], x)
        x = x + self.attn.apply(params["attn"], h, train=train)
        return x + self._mlp(params, self.ln2.apply(params["ln2"], x))

    def _mlp(self, params, h):
        h = self.fc1.apply(params["fc1"], h)
        return self.fc2.apply(params["fc2"], h)

    def apply_prefill(self, params, x):
        """Forward + the attention K/V cache (dense decode path)."""
        h = self.ln1.apply(params["ln1"], x)
        a, cache = self.attn.apply_prefill(params["attn"], h)
        x = x + a
        return x + self._mlp(params, self.ln2.apply(params["ln2"], x)), cache

    def apply_decode(self, params, x1, cache, pos):
        h = self.ln1.apply(params["ln1"], x1)
        a, cache = self.attn.apply_decode(params["attn"], h, cache, pos)
        x1 = x1 + a
        return (x1 + self._mlp(params, self.ln2.apply(params["ln2"], x1)),
                cache)


class MoEBlock(Block):
    """Transformer block whose MLP is a Switch-style top-1 mixture of
    experts (``parallel/moe.py``), expert-parallel over ``'model'`` when
    ``ep > 1``.  ``apply`` returns ``(y, aux)`` — the load-balance loss rides
    up to the model's loss head."""

    def __init__(self, dim, n_head, n_experts, mlp_ratio=4, cd=jnp.bfloat16,
                 tp=1, sp=1, capacity_factor=1.25, top_k=1,
                 attn_impl="reference", name="moe_block"):
        # attention (and its specs) come from Block; tp doubles as the
        # expert-parallel degree — both shard over the same 'model' axis.
        # sp>1 (round-4): tokens are sequence-sharded — with tp==1 the
        # experts shard over 'seq' instead (all-to-all dispatch,
        # parallel/moe.py); with tp>1 they stay on 'model' and only the
        # aux statistic averages over 'seq'.
        super().__init__(dim, n_head, mlp_ratio=mlp_ratio, cd=cd, tp=tp,
                         sp=sp, attn_impl=attn_impl, name=name)
        from ..parallel.moe import MoE
        self.moe = MoE(dim, n_experts, mlp_ratio=mlp_ratio, ep=tp,
                       seq_shards=sp, top_k=top_k,
                       capacity_factor=capacity_factor, compute_dtype=cd,
                       name="moe")
        del self.fc1, self.fc2

    def init(self, key):
        ks = jax.random.split(key, 4)
        return {"ln1": self.ln1.init(ks[0]), "attn": self.attn.init(ks[1]),
                "ln2": self.ln2.init(ks[2]), "moe": self.moe.init(ks[3])}

    def specs(self):
        s = super().specs()
        ms = self.moe.specs()
        if s is None and ms is None:
            return None
        if s is None:
            # dense attention under sp-sharded experts: attention/LN leaves
            # replicate, only the expert tables shard (over 'seq') — derive
            # the replicated skeleton from the real param structure
            from jax.sharding import PartitionSpec as P

            def skel(layer):
                return jax.tree.map(lambda _: P(), jax.eval_shape(
                    layer.init, jax.random.key(0)))

            s = {"ln1": skel(self.ln1), "ln2": skel(self.ln2),
                 "attn": skel(self.attn)}
        else:
            del s["fc1"], s["fc2"]
        s["moe"] = ms
        return s

    def apply(self, params, x, *, train=False, rng=None, state=None):
        h = self.ln1.apply(params["ln1"], x)
        x = x + self.attn.apply(params["attn"], h, train=train)
        h = self.ln2.apply(params["ln2"], x)
        y, aux = self.moe.apply(params["moe"], h, train=train)
        return x + y, aux

    # the MoE FFN is per-token (routing included), so the KV-decode path
    # works like the dense block's — the load-balance aux is a TRAINING
    # statistic and is discarded at inference
    supports_kv_decode = True

    def _mlp(self, params, h):
        y, _aux = self.moe.apply(params["moe"], h)
        return y


class TransformerLM(ModelBase):
    batch_size = 16
    epochs = 10
    n_subb = 1
    learning_rate = 3e-3
    optimizer = "adam"
    weight_decay = 0.0
    momentum = 0.9
    vocab = 64
    d_model = 128
    n_head = 4
    n_layer = 2
    seq_len = 64

    tp = 1          # tensor-parallel degree (mesh gains a 'model' axis)
    pp = 1          # pipeline-parallel degree (mesh gains a 'pipe' axis)
    sp = 1          # sequence-parallel degree (mesh gains a 'seq' axis)
    pp_microbatches = 0   # microbatches streamed per step (0 → 2·pp)
    pp_interleave = 1     # virtual layer chunks per pipeline stage (v):
    #   v>1 interleaves non-contiguous chunks so the pipeline bubble drops
    #   from (pp−1)/(M+pp−1) to (pp−1)/(v·M+pp−1) — parallel/pipeline.py

    def build_model(self) -> None:
        cd = self.config.get("compute_dtype", jnp.bfloat16)
        for k in ("vocab", "d_model", "n_head", "n_layer", "seq_len", "tp",
                  "pp", "sp", "pp_microbatches", "pp_interleave"):
            if k in self.config:
                setattr(self, k, int(self.config[k]))
        if self.sp > 1:
            from ..parallel.mesh import SEQ_AXIS
            assert self.mesh.shape.get(SEQ_AXIS) == self.sp, (
                f"sp={self.sp} needs a mesh with a '{SEQ_AXIS}' axis of "
                f"that size (worker_mesh(n, sp={self.sp})); got "
                f"{dict(self.mesh.shape)}")
            assert self.seq_len % self.sp == 0, (
                f"seq_len={self.seq_len} not divisible by sp={self.sp}")
        if self.pp > 1:
            from ..parallel.mesh import PIPE_AXIS
            assert self.mesh.shape.get(PIPE_AXIS) == self.pp, (
                f"pp={self.pp} needs a mesh with a '{PIPE_AXIS}' axis of "
                f"that size (worker_mesh(n, pp={self.pp})); got "
                f"{dict(self.mesh.shape)}")
            assert self.n_layer % self.pp == 0, (
                f"n_layer={self.n_layer} not divisible by pp={self.pp}")
            if not self.pp_microbatches:
                self.pp_microbatches = 2 * self.pp
        if self.pp_interleave > 1:
            # interleaved virtual stages: v chunks of L/(pp·v) layers per
            # device — pipeline_apply re-validates at trace time; fail at
            # build time with the config knobs named
            if self.pp == 1:
                raise ValueError(
                    f"pp_interleave={self.pp_interleave} needs pipeline "
                    f"parallelism — set the 'pp' config knob > 1 (got "
                    f"pp={self.pp})")
            if self.n_layer % (self.pp * self.pp_interleave):
                raise ValueError(
                    f"n_layer={self.n_layer} not divisible by "
                    f"pp*pp_interleave={self.pp * self.pp_interleave} "
                    f"(config knobs 'n_layer', 'pp', 'pp_interleave')")
            if self.pp_microbatches % self.pp:
                raise ValueError(
                    f"pp_microbatches={self.pp_microbatches} not divisible "
                    f"by pp={self.pp} — the interleaved schedule streams "
                    f"microbatches in groups of pp (config knob "
                    f"'pp_microbatches')")
        if self.tp > 1:
            from ..parallel import tp as tplib
            assert self.mesh.shape.get(tplib.MODEL_AXIS) == self.tp, (
                f"tp={self.tp} needs a mesh with a '{tplib.MODEL_AXIS}' axis "
                f"of that size (worker_mesh(n, tp={self.tp})); got "
                f"{dict(self.mesh.shape)}")
            self.embed = tplib.VocabParallelEmbedding(
                self.vocab, self.d_model, self.tp, compute_dtype=cd)
        else:
            self.embed = L.Embedding(self.vocab, self.d_model,
                                     compute_dtype=cd)
        self.pos = L.Embedding(self.seq_len, self.d_model, compute_dtype=cd,
                               name="pos")
        attn_impl = str(self.config.get("attn_impl", "reference"))
        if attn_impl == "flash":
            # fail at build time, not with an opaque Pallas lowering error
            assert self.seq_len % 128 == 0, (
                f"attn_impl='flash' needs seq_len a multiple of the "
                f"kernel's 128-wide blocks; got {self.seq_len}")
        self.blocks = [Block(self.d_model, self.n_head, cd=cd, tp=self.tp,
                             sp=self.sp, attn_impl=attn_impl,
                             name=f"block{i}")
                       for i in range(self.n_layer)]
        self.ln_f = L.LayerNorm(self.d_model, name="ln_f")
        # under tp the head is column-parallel over the VOCAB; the loss works
        # directly on the sharded logits (vocab-parallel cross-entropy)
        self.head = L.FC(self.d_model, self.vocab, w_init=("normal", 0.02),
                         activation=None, compute_dtype=cd, name="head")
        if self.config.get("data_dir"):
            # real corpus: nanoGPT-style flat token files, memory-mapped
            from .data.tokens import TokenFileData
            self.data = TokenFileData(self.config, self.batch_size,
                                      self.seq_len, vocab=self.vocab)
        else:
            self.data = LMData(self.config, self.batch_size)

    def param_specs(self):
        from jax.sharding import PartitionSpec as P
        if self.pp == 1 and self.tp == 1:
            blk = {b.name: b.specs() for b in self.blocks}
            if all(v is None for v in blk.values()):
                return None
            # sp-sharded MoE experts in an otherwise replicated model
            # (round-4 all-to-all dispatch): dense/attention leaves get a
            # replicated skeleton, expert tables their 'seq' specs
            def skel(b):
                struct = jax.eval_shape(b.init, jax.random.key(0))
                return jax.tree.map(lambda _: P(), struct)

            top = {"embed": {"w": P()}, "pos": {"w": P()},
                   "ln_f": {"scale": P(), "bias": P()},
                   "head": {"w": P(), "b": P()}}
            return {**top,
                    **{b.name: (blk[b.name] if blk[b.name] is not None
                                else skel(b)) for b in self.blocks}}
        if self.tp > 1:
            from ..parallel.mesh import MODEL_AXIS as M
            top = {"embed": {"w": P(M, None)},     # vocab-sharded table
                   "pos": {"w": P()},
                   "ln_f": {"scale": P(), "bias": P()},
                   "head": {"w": P(None, M), "b": P(M)}}
        else:
            top = {"embed": {"w": P()}, "pos": {"w": P()},
                   "ln_f": {"scale": P(), "bias": P()},
                   "head": {"w": P(), "b": P()}}
        if self.pp == 1:
            return {**top, **{blk.name: blk.specs()
                              for blk in self.blocks}}
        # pp: stacked [n_layer, ...] leaves, layer dim over stages — under
        # tp×pp the per-layer tp specs shift right by the stacking dim
        from ..parallel.mesh import PIPE_AXIS
        from ..parallel.steps import _is_spec
        blk = self.blocks[0].specs()
        if blk is None:
            struct = jax.eval_shape(self.blocks[0].init, jax.random.key(0))
            blk = jax.tree.map(lambda _: P(), struct)
        stacked = jax.tree.map(lambda s: P(PIPE_AXIS, *(s or ())), blk,
                               is_leaf=_is_spec)
        return {**top, "blocks": stacked}

    def init_params(self, key):
        ks = jax.random.split(key, len(self.blocks) + 4)
        p = {"embed": self.embed.init(ks[0]), "pos": self.pos.init(ks[1]),
             "ln_f": self.ln_f.init(ks[2]), "head": self.head.init(ks[3])}
        if self.pp > 1:
            # stack the per-layer params [n_layer, ...] from the SAME keys
            # the dense layout would use — pp=k and pp=1 are the same model.
            # The stack order is the interleaved stage permutation (identity
            # at pp_interleave=1): device r's contiguous 'pipe' shard rows
            # ARE its v virtual chunks, so pipeline_apply slices chunks
            # without any runtime gather
            from ..parallel import pipeline as pl
            perm = pl.stage_permutation(self.n_layer, self.pp,
                                        self.pp_interleave)
            p["blocks"] = jax.vmap(self.blocks[0].init)(ks[4:][perm])
            return p
        for i, blk in enumerate(self.blocks):
            p[blk.name] = blk.init(ks[4 + i])
        return p

    def init_bn_state(self):
        return {}

    def batch_spec(self):
        if self.sp > 1:
            from jax.sharding import PartitionSpec as P
            from ..parallel.mesh import SEQ_AXIS, WORKER_AXIS
            return P(WORKER_AXIS, SEQ_AXIS)    # [B rows, T tokens] both cut
        return None

    def _pos_ids(self, t):
        """Position ids for a [B, t] token block: global positions — under
        sp the block is this chip's SLICE of the sequence, offset by the
        seq rank (shared by every forward path, incl. the MoE subclass)."""
        pos_idx = jnp.arange(t)
        if self.sp > 1:
            from ..parallel.mesh import SEQ_AXIS
            pos_idx = pos_idx + jax.lax.axis_index(SEQ_AXIS) * t
        return pos_idx

    def apply_model(self, params, x, *, train, rng, state):
        t = x.shape[1]
        pos_idx = self._pos_ids(t)
        h = self.embed.apply(params["embed"], x) + \
            self.pos.apply(params["pos"], pos_idx)[None]
        if self.pp > 1:
            from ..parallel import pipeline as pl
            tpl = self.blocks[0]

            def stage_fn(stack, hm):
                def body(hh, lp):
                    return tpl.apply(lp, hh, train=train), None
                hh, _ = jax.lax.scan(body, hm, stack)
                return hh

            hm = pl.microbatch(h, self.pp_microbatches)
            hm = pl.pipeline_apply(stage_fn, params["blocks"], hm,
                                   interleave=self.pp_interleave)
            h = pl.unmicrobatch(hm)
        else:
            remat = train and self.config.get("remat", False)
            for blk in self.blocks:
                if remat:
                    # rematerialize each block on the backward pass —
                    # activation memory per block trades for recompute
                    # (jax.checkpoint; the pp path already remats per stage)
                    h = jax.checkpoint(
                        lambda p, x, _b=blk: _b.apply(p, x, train=True))(
                            params[blk.name], h)
                else:
                    h = blk.apply(params[blk.name], h, train=train)
        h = self.ln_f.apply(params["ln_f"], h)
        return self.head.apply(params["head"], h), state

    def loss_and_metrics(self, params, bn_state, batch, rng, train):
        logits, _ = self.apply_model(params, batch["x"], train=train,
                                     rng=rng, state=bn_state)
        v = logits.shape[-1]
        flat = logits.reshape(-1, v)
        y = batch["y"].reshape(-1)
        ls = self._label_smoothing(train)
        if self.tp > 1:
            from ..parallel import tp as tplib
            cost = tplib.tp_softmax_cross_entropy(flat, y,
                                                  label_smoothing=ls)
            err = tplib.tp_errors(flat, y)
        else:
            cost = L.softmax_cross_entropy(flat, y, ls)
            err = L.errors(flat, y)
        if self.sp > 1:
            # per-token means are over the LOCAL token block; average the
            # equal-sized blocks over 'seq' (composes with the tp
            # vocab-parallel CE above: the two reductions are orthogonal)
            from ..parallel.sp import sp_mean
            cost, err = sp_mean(cost), sp_mean(err)
        return cost, (err, bn_state)

    def val_metrics(self, params, bn_state, batch):
        logits, _ = self.apply_model(params, batch["x"], train=False,
                                     rng=None, state=bn_state)
        v = logits.shape[-1]
        flat = logits.reshape(-1, v)
        y = batch["y"].reshape(-1)
        if self.tp > 1:
            from ..parallel import tp as tplib
            cost = tplib.tp_softmax_cross_entropy(flat, y)
            err = tplib.tp_errors(flat, y)
            err5 = tplib.tp_errors_top_x(flat, y, 5)
        else:
            cost = L.softmax_cross_entropy(flat, y)
            err, err5 = L.errors(flat, y), L.errors_top_x(flat, y, 5)
        if self.sp > 1:
            from ..parallel.sp import sp_mean
            cost, err, err5 = sp_mean(cost), sp_mean(err), sp_mean(err5)
        return cost, (err, err5)


    # -- inference ---------------------------------------------------------

    def generate(self, prompt, max_new_tokens: int, temperature: float = 0.0,
                 seed: int = 0, kv_cache: bool = True, params=None):
        """Sample continuations — greedy (``temperature=0``) or categorical.

        One jit-compiled ``lax.scan`` over decode steps on a fixed
        ``[B, seq_len]`` token buffer (static shapes).  ``kv_cache=True``
        (default — dense AND MoE stacks; MoE routing is per-token and
        drop-free at inference): prefill the prompt once, then each step
        projects only the new token and attends to the cached K/V — O(T)
        per token instead of the full O(T²) forward.  ``kv_cache=False``
        keeps the full-forward sampler (pinned near-token-equal).
        Uses the canonical params (EASGD center / GoSGD consensus / BSP
        replica 0 / the EMA shadow) gathered to one device, so it works
        after training under any rule; model-parallel layouts (tp/pp/sp)
        gather the global params and sample through a single-device dense
        twin (same model — dense-parity-pinned).
        """
        if self.tp > 1 or self.pp > 1 or self.sp > 1:
            # model-parallel layouts: gather the global params and sample on
            # a DENSE single-device twin (the layouts are the same model —
            # dense-parity-pinned — so the twin's forward IS this model's)
            return self._dense_twin().generate(
                prompt, max_new_tokens, temperature=temperature, seed=seed,
                kv_cache=kv_cache, params=self._gathered_dense_params())
        import numpy as np

        prompt = np.asarray(prompt, dtype=np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None]
        b, p_len = prompt.shape
        assert p_len >= 1, "generate() needs at least one prompt token"
        assert max_new_tokens >= 1, "generate() needs max_new_tokens >= 1"
        assert p_len + max_new_tokens <= self.seq_len, (
            f"prompt {p_len} + {max_new_tokens} new tokens exceeds "
            f"seq_len={self.seq_len} (the position-embedding table)")

        if params is None:
            params = self.canonical_host_params()
        toks0 = np.zeros((b, self.seq_len), np.int32)
        toks0[:, :p_len] = prompt

        use_kv = kv_cache and all(
            getattr(b, "supports_kv_decode", False) for b in self.blocks)
        if getattr(self, "_gen_jit", None) is None:
            # bound methods + static max_new: jit's own cache memoizes per
            # length, one sampler object per model instance
            self._gen_jit = jax.jit(self._gen_body,
                                    static_argnames=("max_new",))
            self._gen_jit_kv = jax.jit(self._gen_body_kv,
                                       static_argnames=("max_new",))
        fn = self._gen_jit_kv if use_kv else self._gen_jit
        toks, new = fn(params, jnp.asarray(toks0),
                       jnp.int32(p_len), jax.random.key(seed),
                       jnp.float32(temperature),
                       max_new=int(max_new_tokens))
        return np.asarray(jax.device_get(new))

    def _gen_body(self, params, toks, start_pos, key, temp, *, max_new):
        def body(carry, _):
            toks, pos, key = carry
            logits, _ = self.apply_model(params, toks, train=False,
                                         rng=None, state={})
            row = jax.lax.dynamic_index_in_dim(
                logits, pos - 1, axis=1, keepdims=False)       # [B, V]
            nxt, key = self._next_token(row, key, temp)
            toks = jax.lax.dynamic_update_slice(toks, nxt[:, None], (0, pos))
            return (toks, pos + 1, key), nxt

        (toks, _, _), out = jax.lax.scan(body, (toks, start_pos, key), None,
                                         length=max_new)
        return toks, out.T              # [B, max_new]

    def _dense_twin(self):
        """A single-device tp=pp=sp=1 copy of this model (same dims/class),
        built once — the sampler target for model-parallel layouts."""
        if getattr(self, "_twin", None) is None:
            from ..parallel.mesh import worker_mesh
            cfg = {k: v for k, v in self.config.items()
                   if k not in ("mesh", "tp", "pp", "sp", "size", "rank",
                                "pp_microbatches", "pp_interleave",
                                "data_dir")}
            # the sampler never touches the twin's data object — keep its
            # synthetic stream (and memory) minimal instead of re-opening
            # the corpus or materializing the full synthetic arrays
            cfg.update(mesh=worker_mesh(1, devices=jax.devices()[:1]),
                       size=1, rank=0, verbose=False, batch_size=1,
                       synthetic_train=2, synthetic_val=2)
            self._twin = type(self)(cfg)
        return self._twin

    def _gathered_dense_params(self):
        """Global host params reshaped to the DENSE layout: tp/sp gathers
        are already dense-shaped; pp's stacked ``blocks`` leaves unstack
        into per-block subtrees."""
        params = self.canonical_host_params()
        if self.pp == 1:
            return params
        # copy before restructuring: before compile_iter_fns the host params
        # ARE self.params by reference — popping would corrupt the model
        params = dict(params)
        stacked = params.pop("blocks")
        # stacked row j holds depth-order layer perm[j] (interleaved layout;
        # identity at pp_interleave=1) — unstack through the inverse map
        from ..parallel import pipeline as pl
        perm = pl.stage_permutation(self.n_layer, self.pp,
                                    self.pp_interleave)
        inv = np.argsort(perm)
        for i in range(self.n_layer):
            j = int(inv[i])
            params[f"block{i}"] = jax.tree.map(lambda x: x[j], stacked)
        return params

    def _next_token(self, row, key, temp):
        """Greedy/categorical selection from one [B, V] logit row."""
        key, sub = jax.random.split(key)
        greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            sub, row.astype(jnp.float32) /
            jnp.maximum(temp, 1e-6)).astype(jnp.int32)
        return jnp.where(temp > 0, sampled, greedy), key

    def _gen_body_kv(self, params, toks, start_pos, key, temp, *, max_new):
        """KV-cache sampler: one prefill forward builds the per-layer K/V
        caches, then each decode step projects only the new token."""
        t = toks.shape[1]
        h = self.embed.apply(params["embed"], toks) + \
            self.pos.apply(params["pos"], jnp.arange(t))[None]
        caches = []
        for blk in self.blocks:
            h, cache = blk.apply_prefill(params[blk.name], h)
            caches.append(cache)
        # only the row at start_pos-1 is consumed — index BEFORE the [D, V]
        # head projection so prefill doesn't pay a full-buffer head matmul
        h_row = jax.lax.dynamic_index_in_dim(h, start_pos - 1, axis=1)
        row0 = self.head.apply(params["head"],
                               self.ln_f.apply(params["ln_f"], h_row))[:, 0]
        nxt0, key = self._next_token(row0, key, temp)
        toks = jax.lax.dynamic_update_slice(toks, nxt0[:, None],
                                            (0, start_pos))

        def body(carry, _):
            toks, pos, key, caches, tok = carry
            x1 = self.embed.apply(params["embed"], tok[:, None]) + \
                self.pos.apply(params["pos"], pos[None])[None]
            new_caches = []
            for blk, cache in zip(self.blocks, caches):
                x1, cache = blk.apply_decode(params[blk.name], x1, cache,
                                             pos)
                new_caches.append(cache)
            x1 = self.ln_f.apply(params["ln_f"], x1)
            row = self.head.apply(params["head"], x1)[:, 0]
            nxt, key = self._next_token(row, key, temp)
            toks = jax.lax.dynamic_update_slice(toks, nxt[:, None],
                                                (0, pos + 1))
            return (toks, pos + 1, key, tuple(new_caches), nxt), nxt

        (toks, _, _, _, _), rest = jax.lax.scan(
            body, (toks, start_pos, key, tuple(caches), nxt0), None,
            length=max_new - 1)
        out = jnp.concatenate([nxt0[:, None], rest.T], axis=1)
        return toks, out                # [B, max_new]


class MoETransformerLM(TransformerLM):
    """Sparse-FFN variant: every ``moe_every``-th block's MLP is a Switch
    top-1 mixture of ``moe_experts`` experts (``parallel/moe.py``).  Under
    ``tp > 1`` the experts are SHARDED over the ``'model'`` axis (expert
    parallelism) while attention stays tensor-parallel on the same axis.
    The Switch load-balance loss is added to the objective with coefficient
    ``moe_aux`` and surfaced per-step via ``current_info``-style cost."""

    moe_experts = 4
    moe_every = 2          # every k-th block is MoE (1 = all blocks)
    moe_topk = 1           # experts per token (2 = GShard-style top-2)
    moe_aux = 0.01
    capacity_factor = 1.25

    def build_model(self) -> None:
        super().build_model()
        cd = self.config.get("compute_dtype", jnp.bfloat16)
        for k in ("moe_experts", "moe_every", "moe_topk"):
            if k in self.config:
                setattr(self, k, int(self.config[k]))
        assert self.pp == 1 or self.moe_every == 1, (
            "pipeline parallelism needs a homogeneous block stack: the "
            "mixed MoE/dense stack (moe_every > 1) does not stack over "
            "'pipe'; use moe_every=1 (every block MoE) with pp")
        for k in ("moe_aux", "capacity_factor"):
            if k in self.config:
                setattr(self, k, float(self.config[k]))
        if self.tp > 1:
            assert self.moe_experts % self.tp == 0, (
                f"moe_experts={self.moe_experts} not divisible by "
                f"tp/ep={self.tp}")
        if self.sp > 1 and self.tp == 1:
            assert self.moe_experts % self.sp == 0, (
                f"moe_experts={self.moe_experts} not divisible by "
                f"sp={self.sp} (experts shard over 'seq')")
        attn_impl = str(self.config.get("attn_impl", "reference"))
        self.blocks = [
            MoEBlock(self.d_model, self.n_head, self.moe_experts, cd=cd,
                     tp=self.tp, sp=self.sp,
                     capacity_factor=self.capacity_factor,
                     top_k=self.moe_topk,
                     attn_impl=attn_impl, name=f"block{i}")
            if (i + 1) % self.moe_every == 0 else
            Block(self.d_model, self.n_head, cd=cd, tp=self.tp, sp=self.sp,
                  attn_impl=attn_impl, name=f"block{i}")
            for i in range(self.n_layer)]

    def _forward(self, params, x, *, train):
        t = x.shape[1]
        h = self.embed.apply(params["embed"], x) + \
            self.pos.apply(params["pos"], self._pos_ids(t))[None]
        if self.pp > 1:
            # homogeneous all-MoE stack over 'pipe': each stage's aux rides
            # the pipeline (bubble ticks masked), normalized to the dense
            # layout's mean-aux-per-layer
            from ..parallel import pipeline as pl
            tpl = self.blocks[0]

            def stage_fn(stack, hm):
                def body(carry, lp):
                    hh, aux = carry
                    y, a = tpl.apply(lp, hh, train=train)
                    return (y, aux + a), None

                # zero scalar derived from ONE element of hm so the scan
                # carry inherits its full set of varying mesh axes (fresh
                # zeros would be device-invariant and fail the carry typing;
                # a full-tensor reduce would pay O(mb·T·D) per tick)
                aux0 = hm.reshape(-1)[0].astype(jnp.float32) * 0
                (hh, aux), _ = jax.lax.scan(body, (hm, aux0), stack)
                return hh, aux

            hm = pl.microbatch(h, self.pp_microbatches)
            hm, aux_sum = pl.pipeline_apply(stage_fn, params["blocks"], hm,
                                            with_aux=True,
                                            interleave=self.pp_interleave)
            h = pl.unmicrobatch(hm)
            # KNOWN DEVIATION from the dense layout: this is the mean of
            # per-MICROBATCH load-balance losses, not the aux of the full
            # batch's routing fractions — microbatch f_e/P_e are noisier, so
            # the pp objective differs slightly from dense (the main loss is
            # pinned equal; the aux parity claim is scoped to dense/tp/ep)
            aux = aux_sum / (self.pp_microbatches * self.n_layer)
            if self.sp > 1:
                # each microbatch aux is seq-invariant (pmean'd in the MoE
                # layer) but the scan carry was seeded from a seq-VARYING
                # zero for its axis typing — re-anchor bit-exactly so the
                # loss out-spec sees the invariance
                from ..parallel.mesh import SEQ_AXIS
                from ..parallel.steps import anchor_invariant
                aux = anchor_invariant(aux, (SEQ_AXIS,))
        else:
            aux = jnp.zeros((), jnp.float32)
            n_moe = 0
            for blk in self.blocks:
                out = blk.apply(params[blk.name], h, train=train)
                if isinstance(blk, MoEBlock):
                    h, a = out
                    aux = aux + a
                    n_moe += 1
                else:
                    h = out
            aux = aux / max(n_moe, 1)
        h = self.ln_f.apply(params["ln_f"], h)
        logits = self.head.apply(params["head"], h)
        return logits, aux

    def apply_model(self, params, x, *, train, rng, state):
        logits, _ = self._forward(params, x, train=train)
        return logits, state

    def loss_and_metrics(self, params, bn_state, batch, rng, train):
        logits, aux = self._forward(params, batch["x"], train=train)
        v = logits.shape[-1]
        flat = logits.reshape(-1, v)
        y = batch["y"].reshape(-1)
        ls = self._label_smoothing(train)
        if self.tp > 1:
            from ..parallel import tp as tplib
            cost = tplib.tp_softmax_cross_entropy(flat, y,
                                                  label_smoothing=ls)
            err = tplib.tp_errors(flat, y)
        else:
            cost = L.softmax_cross_entropy(flat, y, ls)
            err = L.errors(flat, y)
        if self.sp > 1:
            # per-token CE/err are over the local token block; the aux is
            # already seq-invariant (pmean'd inside the MoE layer)
            from ..parallel.sp import sp_mean
            cost, err = sp_mean(cost), sp_mean(err)
        return cost + self.moe_aux * aux, (err, bn_state)
