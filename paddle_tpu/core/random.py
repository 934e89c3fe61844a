"""Global RNG state.

Parity surface: ``paddle.seed`` / generator-per-device (upstream:
paddle/phi/core/generator.h). TPU-native design: the state is a jax PRNG key
held in a registered state Tensor, so randomness is (a) reproducible, (b)
functionalized under ``to_static`` — the key becomes a carried jit state and
every compiled step advances it — and (c) splittable for per-device streams
(the RNG-tracker pattern tensor-parallel layers need).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from . import tracing as _tracing
from .tensor import Tensor, register_state_tensor, _is_tracer

__all__ = ["Generator", "default_generator", "seed", "get_rng_state", "set_rng_state"]


def _host_key(seed_val: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed_val)`` as a host array, computed without
    jax: the default generator is built at import, and an import must not
    initialise a backend (the chip belongs to one process — a launcher or
    fleet-supervisor parent that imports the package has to stay off it).
    Raw threefry2x32 key of a 32-bit seed: ``[0, seed mod 2**32]``;
    ``tests/test_bring_up.py`` pins it against jax."""
    return np.array([0, int(seed_val) & 0xFFFFFFFF], dtype=np.uint32)


class Generator:
    def __init__(self, seed_val: int = 0, name: Optional[str] = None):
        self._key = Tensor(_host_key(seed_val), stop_gradient=True,
                           name=name or "rng_state")
        self._key.persistable = True
        register_state_tensor(self._key)

    def manual_seed(self, seed_val: int) -> "Generator":
        self._key._set_data(jax.random.PRNGKey(seed_val))
        return self

    def split_key(self):
        """Return a fresh subkey; advances (and trace-logs) the state."""
        ts = _tracing.trace_state()
        key = self._key._data
        if ts is not None and not _is_tracer(key):
            ts.record_read(self._key)
        next_key, sub = jax.random.split(key)
        self._key._set_data(next_key)
        return sub

    @property
    def state(self) -> Tensor:
        return self._key

    def get_state(self):
        # snapshot, not the live state tensor — saved states must not advance
        # with the generator (paddle.get_rng_state contract)
        return Tensor(self._key._data, stop_gradient=True)

    def set_state(self, state) -> None:
        self._key._set_data(state._data if isinstance(state, Tensor) else state)


default_generator = Generator(0)


def seed(seed: int) -> Generator:
    """``paddle.seed`` parity (upstream names the arg ``seed``)."""
    default_generator.manual_seed(int(seed))
    return default_generator


def get_rng_state():
    return [default_generator.get_state()]


def set_rng_state(states) -> None:
    default_generator.set_state(states[0] if isinstance(states, (list, tuple)) else states)
