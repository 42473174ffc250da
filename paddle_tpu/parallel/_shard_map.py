"""The one ``shard_map`` spelling of the framework.

Every shard_map call routes through here, so the mesh/spec keyword
form and the ``check_vma`` default live in one place.
"""

from __future__ import annotations

from typing import Any, Optional

import jax

__all__ = ["shard_map"]


def shard_map(f, mesh, in_specs, out_specs,
              check_vma: Optional[bool] = None) -> Any:
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
