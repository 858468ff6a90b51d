"""Shared fixtures. NOTE: no XLA_FLAGS here — unit tests see 1 device;
distributed behaviour is tested via subprocesses (test_distributed.py)."""

import math
import re

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


_POOL_MOVES = ("copy", "broadcast", "dynamic-slice", "dynamic-update-slice",
               "concatenate")
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]"
                     r"(?:\{[^}]*\})? ([\w\-]+)\(")


def _pool_moves(hlo_text, pool_shape):
    """Lines of optimized HLO that copy, fill, slice, update-slice or
    concatenate a buffer the size of the paged pool: the stacked
    ``(La, P, KV, bs, hd)`` code plane, one layer of it, or the matching
    f32 scale planes (sizes, not shapes, so no reshaped view escapes).
    Scatters, parameters, tuples and bitcasts are not moves."""
    La, P, KV, bs, hd = pool_shape
    sizes = {("u8", La * P * KV * bs * hd), ("u8", P * KV * bs * hd),
             ("f32", La * P * KV * bs), ("f32", P * KV * bs)}
    found = []
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if m is None or m.group(3) not in _POOL_MOVES:
            continue
        dims = m.group(2)
        n = math.prod(int(d) for d in dims.split(",")) if dims else 1
        if (m.group(1), n) in sizes:
            found.append(line.strip())
    return found


@pytest.fixture
def pool_moves():
    """:func:`_pool_moves`: the structural guard on the paged steps."""
    return _pool_moves


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
    config.addinivalue_line(
        "markers", "multidevice: runs natively only under the forced-"
        "multi-device CI shard (XLA_FLAGS host device count >= 8)")
