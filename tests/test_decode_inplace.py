"""The KV cache stays in place through the compiled decode programs.

The layer scan carries each stage's stacked K/V cache and writes only the
new rows (``models/model.py::_apply_stage``, ``models/layers.py::
_cache_write``). Whether XLA then updates the donated cache in place is a
property of the compiled program, not of the tokens, so these tests compile
the slot-serving decode program and ``generate``'s decode scan on the CPU
and read two counters from them: the temporary bytes the program allocates,
and the ``copy`` instructions whose shape is a K/V cache leaf's. Each config
is sized so that the cache dominates every other temporary (logits,
activations, one layer's slab): a program that copied the cache once would
need at least the whole cache as temp.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.infer import Engine
from repro.models import init_params, reduced
from repro.models import layers as L

KEY = jax.random.PRNGKey(0)
SLOTS, MAX_SEQ, STEPS = 8, 512, 4

CONFIGS = {
    # dense GQA: every layer a full-length K/V cache
    "dense": lambda: reduced(get_config("llama3.2-3b"), n_layers=4),
    # local attention only: ring buffers of `window` rows
    "ring": lambda: reduced(
        get_config("llama3.2-3b"), n_layers=4, window=256,
        stages=((("local_attn",), 4),),
    ),
    # RG-LRU + local attention: recurrent state beside the ring buffers
    "recurrent": lambda: reduced(
        get_config("recurrentgemma-9b"), n_layers=18, window=256
    ),
    # int8 K/V codes with per-(token, head) scales
    "int8": lambda: reduced(
        get_config("llama3.2-3b"), n_layers=12, kv_cache_dtype="int8"
    ),
}


def _kv_leaf_shapes(cache) -> set:
    shapes = set()

    def visit(path, leaf):
        if L._cache_leaf_name(path) in L.POSITIONAL_CACHE_LEAVES:
            shapes.add(tuple(leaf.shape))

    jax.tree_util.tree_map_with_path(visit, cache)
    return shapes


def _cache_copies(hlo: str, shapes: set) -> list:
    found = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\{[^}]*\} copy\(", hlo):
        shape = tuple(int(d) for d in m.group(1).split(",") if d)
        if shape in shapes:
            found.append(shape)
    return found


def _lower(program: str, eng: Engine):
    """(lowered program, its cache argument)."""
    if program == "decode_slots":
        slots = eng.init_slots(SLOTS)
        lowered = eng._scan_decode_slots.lower(eng.params, slots, n_steps=STEPS)
        return lowered, slots["cache"]
    cache = eng._make_cache(SLOTS)
    lowered = eng._scan_decode.lower(
        eng.params, jnp.zeros((SLOTS, eng.cfg.vocab), jnp.float32), cache,
        jnp.int32(3), KEY, jnp.float32(1.0), n_steps=STEPS, greedy=True,
    )
    return lowered, cache


@pytest.mark.parametrize("program", ["decode_slots", "generate"])
@pytest.mark.parametrize("case", sorted(CONFIGS))
def test_decode_program_keeps_cache_in_place(case, program):
    cfg = CONFIGS[case]()
    eng = Engine(cfg, init_params(KEY, cfg), max_seq=MAX_SEQ)
    lowered, cache = _lower(program, eng)
    compiled = lowered.compile()
    cache_bytes = sum(x.nbytes for x in jax.tree.leaves(cache))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache_bytes / 2, (
        f"{case}/{program}: temp {temp} B against a {cache_bytes} B cache — "
        "the program holds a second copy of the cache"
    )
    copies = _cache_copies(compiled.as_text(), _kv_leaf_shapes(cache))
    assert not copies, f"{case}/{program}: whole-cache copies {copies}"
