"""AlexNet's forward pass in plain float32 (Krizhevsky et al., NIPS 2012,
one-tower layout with the two-group convolutions of the original's two
GPUs).  Evaluation mode: dropout is the identity (the program's dropout is
inverted, so nothing is rescaled at evaluation).

``params`` is the system's own parameter tree (``conv1`` ... ``fc7``,
``softmax``; each ``{"w", "b"}``); ``x`` is ``[N, 227, 227, 3]`` float32,
mean-subtracted.  Departure from the paper, following the program: LRN's
alpha is divided by the window size (see ``plain_ops.lrn``).
"""

from __future__ import annotations

from benchmarks.reference import plain_ops as ops


def forward(params, x):
    h = ops.conv_relu(x, params["conv1"], stride=4)              # 227 -> 55
    h = ops.max_pool(ops.lrn(h), 3, 2)                           # -> 27
    h = ops.conv_relu(h, params["conv2"], pad=2, groups=2)
    h = ops.max_pool(ops.lrn(h), 3, 2)                           # -> 13
    h = ops.conv_relu(h, params["conv3"], pad=1)
    h = ops.conv_relu(h, params["conv4"], pad=1, groups=2)
    h = ops.conv_relu(h, params["conv5"], pad=1, groups=2)
    h = ops.max_pool(h, 3, 2)                                    # -> 6
    h = h.reshape(h.shape[0], -1)
    h = ops.fc(h, params["fc6"])
    h = ops.fc(h, params["fc7"])
    return ops.fc(h, params["softmax"], relu=False)
