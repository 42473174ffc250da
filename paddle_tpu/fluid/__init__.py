"""``paddle.fluid`` migration namespace.

A reference user's ``import paddle.fluid as fluid`` becomes
``import paddle_tpu.fluid as fluid`` and the fluid spellings resolve
(ref surface: python/paddle/fluid/__init__.py:35-78 — framework,
executor, io, layers, dygraph, nets, optimizer, regularizer, metrics,
initializer, clip, profiler, ParamAttr, places, data).

Graph-construction APIs whose semantics inverted in the tracing design
(``default_main_program``/``program_guard``) raise with the working
equivalent named, same policy as ``layers.DynamicRNN``; everything else
routes to working code. ``tests/test_fluid_namespace.py`` drives a
fluid-style train loop end to end through this namespace.
"""

from __future__ import annotations

import contextlib

from .. import clip  # noqa: F401
from .. import io  # noqa: F401
from .. import layers  # noqa: F401
from .. import nets  # noqa: F401
from .. import optimizer  # noqa: F401
from .. import profiler  # noqa: F401
from .. import reader  # noqa: F401
from .. import regularizer  # noqa: F401
from .. import metric as metrics  # noqa: F401
from ..autograd import grad as _grad  # noqa: F401
from ..core.lod import (RaggedBatch, create_lod_tensor,  # noqa: F401
                        create_random_int_lodtensor)
from ..core.place import (CPUPlace, CUDAPlace,  # noqa: F401
                          TPUPlace)

#: pinned host staging has no user-facing device in the TPU design
#: (core/arena.py owns page-aligned staging); alias keeps imports alive
CUDAPinnedPlace = CPUPlace
from ..flags import get_flags, set_flags  # noqa: F401
from ..nn import initializer  # noqa: F401
from ..param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from ..static import (Executor, Program, Scope, data,  # noqa: F401
                      default_main_program, global_scope)
from ..tensor import Tensor  # noqa: F401
from . import dygraph  # noqa: F401

@contextlib.contextmanager
def scope_guard(scope: Scope):
    """(ref: executor.py scope_guard) — run Executor calls against a
    different scope. Swaps the process-global scope for the block;
    Executors resolve the scope at run time, so Executors constructed
    before the guard are covered too."""
    from .. import static as _static
    old = _static._global_scope
    _static._global_scope = scope
    try:
        yield
    finally:
        _static._global_scope = old


# real submodules so `from paddle_tpu.fluid.executor import Executor`
# style imports port unchanged (ref: fluid/__init__.py:35-78)
from . import average  # noqa: E402,F401
from . import backward  # noqa: E402,F401
from . import contrib  # noqa: E402,F401
from . import core  # noqa: E402,F401
from . import executor  # noqa: E402,F401
from . import framework  # noqa: E402,F401
from . import incubate  # noqa: E402,F401
from . import transpiler  # noqa: E402,F401
from . import unique_name  # noqa: E402,F401
from .framework import Variable, in_dygraph_mode  # noqa: E402,F401
from .transpiler import (DistributeTranspiler,  # noqa: E402,F401
                         DistributeTranspilerConfig)

# fluid.input re-exports (ref: fluid/input.py)
embedding = layers.embedding
one_hot = layers.one_hot


def default_startup_program():
    raise NotImplementedError(
        "parameter initialization is eager in the TPU design: layers "
        "initialize on construction (pt.seed(n) for determinism); there "
        "is no startup program to run")


@contextlib.contextmanager
def program_guard(main_program=None, startup_program=None):
    raise NotImplementedError(
        "program construction is tracing: wrap the computation in a "
        "function and build paddle_tpu.static.Program(fn) instead of "
        "recording ops under program_guard")


def is_compiled_with_cuda() -> bool:
    """One answer for both spellings (fluid.is_compiled_with_cuda and
    fluid.framework.is_compiled_with_cuda): True when the process has a
    TPU — CUDAPlace aliases TPUPlace here, so ported
    'CUDAPlace(0) if is_compiled_with_cuda() else CPUPlace()' device
    selection keeps choosing the accelerator."""
    from ..core.place import accelerator_available
    return accelerator_available()


class DataFeeder:
    """(ref: data_feeder.py DataFeeder) — converts a minibatch of
    sample tuples into the feed dict Executor.run takes."""

    def __init__(self, feed_list, place=None, program=None) -> None:
        import numpy as _np

        self._np = _np
        self.names = [f if isinstance(f, str) else getattr(f, "name", None)
                      or str(f) for f in feed_list]
        self.place = place

    def feed(self, iterable):
        cols = list(zip(*iterable))
        if len(cols) != len(self.names):
            raise ValueError(
                f"DataFeeder: batch rows have {len(cols)} fields for "
                f"{len(self.names)} feed names {self.names}")
        out = {}
        for n, col in zip(self.names, cols):
            arrs = [self._np.asarray(v) for v in col]
            if len({a.shape for a in arrs}) > 1:
                raise ValueError(
                    f"DataFeeder: field {n!r} has ragged sample shapes "
                    f"{sorted({a.shape for a in arrs})}. LoD-style "
                    "variable-length feeding is a dense redesign here: "
                    "pad to a fixed seq_len and pass lengths as their "
                    "own field (see paddle_tpu.ops.sequence — every op "
                    "takes (x, length))")
            out[n] = self._np.stack(arrs)
        return out
