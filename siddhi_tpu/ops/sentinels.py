"""Shared device-step sentinel constants.

Numpy scalars, NOT jnp: a jnp constant built at import initializes the
device backend in whatever process imports the module (a parent that
only spawns children would then hold the chip), and one captured by a
jitted step is one more device buffer per program; numpy scalars embed
as HLO literals. Keep every module-level constant that jitted code
touches in numpy.
"""
import numpy as np

NEG_INF = np.int64(-(2 ** 62))
POS_INF = np.int64(2 ** 62)
I32_MAX = np.int32(2 ** 31 - 1)
I32_LO = -(2 ** 31) + 1

# sentinel for "row not placed in any slot" (keyed state, partitions)
NO_SLOT = np.int32(-1)
