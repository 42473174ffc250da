"""RNG state management.

Analogue of the reference's Generator (/root/reference/paddle/fluid/
framework/generator.cc — global per-device RNG state) redesigned for JAX's
functional, key-based PRNG:

- Eager mode keeps a global stateful :class:`Generator` whose ``split()``
  advances an internal key — matching the reference's "global seed" UX.
- Under ``jit`` tracing, stateful splitting would bake one fixed key into the
  compiled program. Traced code must instead draw keys from a *bound stream*
  (:func:`rng_scope`), which the Layer/executor machinery seeds per step with
  a key threaded through the step's functional state. ``split()`` inside a
  scope folds a trace-time counter into the bound key, so every dropout call
  site gets a distinct, step-varying key without retracing.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp

_fast_rng_configured = False
_fast_rng_lock = threading.Lock()


def _configure_fast_rng_once() -> None:
    """Switch to the hardware RngBitGenerator PRNG on TPU (FLAGS_use_fast_rng).

    Must run before the FIRST jax.random key is created anywhere in the
    package: mixing PRNG impls in one process breaks stream
    reproducibility. The generator serves initialisation and small
    draws; dropout masks draw no words from it (they hash a seed folded
    from the key: ``ops.nn_functional.dropout_keep_mask``), so what it
    is worth to a train step has not been measured in this tree. Called
    lazily from Generator key creation so that ``import paddle_tpu``
    never initializes the PJRT backend (a slow or contended accelerator
    plugin would hang the import otherwise).
    """
    global _fast_rng_configured
    with _fast_rng_lock:
        if _fast_rng_configured:
            return
        from .. import flags

        if flags.GLOBAL_FLAGS.get("use_fast_rng") \
                and jax.default_backend() == "tpu":
            jax.config.update("jax_default_prng_impl", "rbg")
        _fast_rng_configured = True


def mix32(x):
    """The two multiply-xorshift rounds of the murmur3 finalizer
    (uint32 in/out): every input bit reaches the high bits of the
    result, which is what a threshold reads. Plain jnp, so it runs
    inside a Pallas kernel too."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    return x


def fmix32(x):
    """murmur3 finalizer: ``mix32`` and a last fold of the high half
    into the low one, a full-avalanche 32-bit mix. The one mixing
    function of every counter-hash keep-mask: the flash kernels'
    attention dropout and ``ops.nn_functional``'s element-wise one."""
    x = mix32(x)
    return x ^ (x >> jnp.uint32(16))


def make_key(seed) -> jax.Array:
    """Create a PRNG key, applying the fast-RNG backend config first.

    Every key creation in the package must go through here (or through
    ``Generator.split``) so the FLAGS_use_fast_rng switch to the TPU
    RngBitGenerator impl lands before the first key exists — mixing PRNG
    impls in one process breaks stream reproducibility.
    """
    _configure_fast_rng_once()
    return jax.random.key(seed)


class Generator:
    """Stateful PRNG-key source for eager mode.

    Key creation is lazy: no JAX backend is touched until the first
    ``split()`` — keeping ``import paddle_tpu`` accelerator-free.
    """

    def __init__(self, seed: int = 0) -> None:
        self._seed = seed
        self._key = None
        self._lock = threading.Lock()

    def manual_seed(self, seed: int) -> "Generator":
        with self._lock:
            self._seed = seed
            self._key = None
        return self

    @property
    def initial_seed(self) -> int:
        return self._seed

    def split(self) -> jax.Array:
        with self._lock:
            if self._key is None:
                self._key = make_key(self._seed)
            self._key, sub = jax.random.split(self._key)
            return sub


_default_generator = Generator(0)


def default_generator() -> Generator:
    return _default_generator


def seed(value: int) -> Generator:
    """Global seed — mirrors ``paddle.seed``."""
    return _default_generator.manual_seed(value)


class _RngStream:
    """A bound key plus a trace-time call counter."""

    def __init__(self, key: jax.Array) -> None:
        self.key = key
        self.count = 0

    def next(self) -> jax.Array:
        sub = jax.random.fold_in(self.key, self.count)
        self.count += 1
        return sub


class _ScopeState(threading.local):
    def __init__(self) -> None:
        self.streams: Optional[Dict[str, _RngStream]] = None


_scope = _ScopeState()


@contextlib.contextmanager
def rng_scope(**keys: jax.Array) -> Iterator[None]:
    """Bind named key streams (e.g. ``dropout=key``) for traced code."""
    prev = _scope.streams
    _scope.streams = {name: _RngStream(k) for name, k in keys.items()}
    try:
        yield
    finally:
        _scope.streams = prev


def next_key(stream: str = "default") -> jax.Array:
    """Draw the next key: from the bound scope if present, else eagerly."""
    if _scope.streams is not None:
        if stream in _scope.streams:
            return _scope.streams[stream].next()
        if "default" in _scope.streams:
            return _scope.streams["default"].next()
    return _default_generator.split()


def in_rng_scope() -> bool:
    return _scope.streams is not None
