"""VGG-16's forward pass in plain float32 (Simonyan and Zisserman,
arXiv:1409.1556, configuration D): thirteen 3x3 convolutions with padding 1
in blocks of 2, 2, 3, 3, 3, a 2x2/2 max-pool after each block, then
FC 4096, 4096, 1000.  Evaluation mode: dropout is the identity.

``params`` is the system's own parameter tree (``conv<block>_<i>``, ``fc6``,
``fc7``, ``softmax``); ``x`` is ``[N, 224, 224, 3]`` float32.
"""

from __future__ import annotations

from benchmarks.reference import plain_ops as ops

BLOCKS = (2, 2, 3, 3, 3)


def forward(params, x):
    h = x
    for block, reps in enumerate(BLOCKS, start=1):
        for i in range(1, reps + 1):
            h = ops.conv_relu(h, params[f"conv{block}_{i}"], pad=1)
        h = ops.max_pool(h, 2, 2)
    h = h.reshape(h.shape[0], -1)
    h = ops.fc(h, params["fc6"])
    h = ops.fc(h, params["fc7"])
    return ops.fc(h, params["softmax"], relu=False)
