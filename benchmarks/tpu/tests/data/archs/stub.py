"""A stand-in architecture for the benchmark's tests.

It declares linears whose layer counts differ from each other and from
``n_layers``, and attention in fewer layers than ``n_layers``, so a test can
tell whether the harness took its work from here. Its weights, program tree
and reference are the dense module's, so a configuration that names it is
served; each call is recorded in ``CALLS``.
"""

from tpubench import arch

_dense = arch.load("dense")
CALLS = []


def linears(mc):
    d, f, v = mc["d_model"], mc["d_ff"], mc["vocab"]
    return [("mix", d, 2 * d, 3), ("up", d, f, 1), ("down", f, d, 2), (arch.HEAD, d, v, 1)]


def attention_layers(mc):
    return 1


def weights_fn(config):
    CALLS.append("weights_fn")
    return _dense.weights_fn(config)


def program_params(weights, config):
    CALLS.append("program_params")
    return _dense.program_params(weights, config)


def forward(mc, g, control, block, w, tokens, rows):
    CALLS.append("forward")
    return _dense.forward(mc, g, control, block, w, tokens, rows)
