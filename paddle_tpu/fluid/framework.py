"""``fluid.framework`` surface (ref: python/paddle/fluid/framework.py).

The graph-description machinery (Program/Block/OpDesc) inverted into
tracing; the names user code actually touches route here."""

from __future__ import annotations

from ..core.place import CPUPlace, CUDAPlace  # noqa: F401
from ..core.place import is_compiled_with_cuda  # noqa: F401
from ..nn.layer import Parameter  # noqa: F401
from ..static import (Program, default_main_program,  # noqa: F401
                      global_scope)
from ..tensor import Tensor

Variable = Tensor  # traced arrays fill the Variable role


def in_dygraph_mode() -> bool:
    """Eager is always on (the mode switch collapsed under jit)."""
    return True


def _non_static_mode() -> bool:
    return True
