"""The toy model's forward pass in plain float32."""

from benchmarks.reference import plain_ops as ops


def forward(params, x):
    h = ops.conv_relu(x, params["conv1"], stride=2, pad=1)
    h = ops.max_pool(h, 2, 2)
    h = h.reshape(h.shape[0], -1)
    h = ops.fc(h, params["fc2"])
    return ops.fc(h, params["softmax"], relu=False)
