"""Compiled cost-model feedback: `hapi.flops.flops_compiled` reads XLA's
own cost analysis (reference analog: `hapi/dynamic_flops.py`)."""
import numpy as np

import paddle_tpu.nn as nn


def test_flops_compiled_matches_analytic():
    from paddle_tpu.hapi.flops import flops_compiled

    net = nn.Linear(64, 128, bias_attr=False)
    x = np.zeros((32, 64), np.float32)
    got = flops_compiled(lambda t: net(t), [x])
    analytic = 2 * 32 * 64 * 128                      # mul+add
    assert 0.5 * analytic <= got["flops"] <= 2 * analytic, got
    assert got["bytes_accessed"] > 0
    # full backward differentiates w.r.t. params too: the dL/dW
    # contraction (x^T @ g) must show up, so backward >= forward even
    # for a single linear layer
    b = flops_compiled(lambda t: net(t), [x], backprop=True, net=net)
    assert b["flops"] >= got["flops"], (got, b)
    mlp = nn.Sequential(nn.Linear(64, 128), nn.Tanh(),
                        nn.Linear(128, 64))
    f2 = flops_compiled(lambda t: mlp(t), [x])
    b2 = flops_compiled(lambda t: mlp(t), [x], backprop=True, net=mlp)
    assert b2["flops"] > 1.5 * f2["flops"], (f2, b2)
