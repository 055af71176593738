"""``jax.random.fold_in`` of a legacy threefry key, on the host.

A prefill's sampling key is ``fold_in(engine key, step count)``. Folded
by jax that is an eager device call (on a TPU two dispatches, about a
millisecond of host) made with nothing queued on the chip, directly in
front of the prefill's own launch (PERF.md §6, PR 46). The fold is
twenty rounds of 32-bit adds, rotates and xors over two words, so the
engine does it here in Python integers and hands the prefill the same
64 bits as a host value (tests/test_serving.py holds them to jax's, bit
for bit). ``_decode_jit`` folds its key inside its program and needs
none of this.
"""

import numpy as np

_MASK = 0xFFFFFFFF
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def fold_in(key_words, count):
    """``np.asarray(jax.random.fold_in(key, count))`` for a legacy
    ``uint32[2]`` threefry key whose two words are ``key_words`` and a
    ``count`` in [0, 2**32): threefry-2x32 of the block ``[0, count]``."""
    k0, k1 = int(key_words[0]), int(key_words[1])
    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    a, b = k0, (count + k1) & _MASK
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            a = (a + b) & _MASK
            b = ((b << r) & _MASK | b >> (32 - r)) ^ a
        a = (a + ks[(i + 1) % 3]) & _MASK
        b = (b + ks[(i + 2) % 3] + i + 1) & _MASK
    return np.array([a, b], np.uint32)
