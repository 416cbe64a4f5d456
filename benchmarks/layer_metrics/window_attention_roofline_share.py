"""Layer: mixed attention.  The least time the chip could take for the
attention cores' required FLOPs (scores and weighted sums over the keys a
query may see, forward and backward, every layer: the causal half in a
full-attention layer, the window in a window layer; the configuration's
counter under ``flops/``, ``attn_core_train_flops_per_sample``;
compute-bound, FLOPs / bf16 peak) as a percentage of the device time under
the scope ``attn_core``, whatever computes it.  The time holds the forward
pass the backward makes again; the FLOPs do not.  The same reading as the
looped cell's ``attention_roofline_share``, of another layer: its reader
is this one's."""

from benchmarks.layer_metrics.attention_roofline_share import read  # noqa: F401,E501
