"""Device identity ("Place") layer.

TPU-native analogue of the reference's Place/DeviceContext/DeviceContextPool
(/root/reference/paddle/fluid/platform/place.h, device_context.h, and
init.cc:141 InitDevices). PJRT owns streams/contexts, so the layer reduces
to: tagged device identity objects (CPUPlace/TPUPlace), device enumeration,
and a default-device selector that maps onto ``jax.default_device``. The
``selected_devices`` flag mirrors FLAGS_selected_gpus.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Union

import jax

from ..flags import GLOBAL_FLAGS


class Place:
    device_type = "unspecified"

    def __init__(self, device_id: int = 0) -> None:
        self.device_id = device_id

    def __eq__(self, other) -> bool:
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self) -> int:
        return hash((type(self).__name__, self.device_id))

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self):
        devs = jax.devices(self.device_type)
        return devs[self.device_id % len(devs)]


class CPUPlace(Place):
    device_type = "cpu"

    def jax_device(self):
        return jax.devices("cpu")[0]


class TPUPlace(Place):
    """The accelerator place. ``jax_device()`` raises (jax's
    RuntimeError) when the process has no TPU backend: asking for the
    chip never quietly hands back a CPU device."""

    device_type = "tpu"


# API parity alias: reference code says CUDAPlace for the accelerator.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace


@functools.lru_cache(maxsize=None)
def accelerator_available() -> bool:
    """THE accelerator predicate: does the default backend hold TPU
    devices. Initializes the backend; one that fails to initialise
    raises here rather than reading as "no accelerator"."""
    return any(d.platform == "tpu" for d in jax.devices())


is_compiled_with_tpu = accelerator_available
# reference-parity spelling
is_compiled_with_cuda = accelerator_available


_current_place: Optional[Place] = None


def set_device(device: Union[str, Place]) -> Place:
    """'tpu', 'tpu:0', 'cpu' — mirrors paddle.set_device."""
    global _current_place
    if isinstance(device, Place):
        place = device
    else:
        name, _, idx = device.partition(":")
        idx = int(idx) if idx else 0
        if name in ("tpu", "gpu", "cuda", "xpu"):
            place = TPUPlace(idx)
        elif name == "cpu":
            place = CPUPlace(idx)
        else:
            raise ValueError(f"unknown device '{device}'")
    # resolve first: a place with no device behind it raises and leaves
    # the current selection untouched
    jax.config.update("jax_default_device", place.jax_device())
    _current_place = place
    return place


def get_device() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = TPUPlace(0) if accelerator_available() \
            else CPUPlace(0)
    return _current_place


def device_count() -> int:
    sel = GLOBAL_FLAGS.get("selected_devices")
    if sel:
        return len([s for s in sel.split(",") if s.strip() != ""])
    return jax.device_count()


def local_devices() -> List:
    devs = jax.local_devices()
    sel = GLOBAL_FLAGS.get("selected_devices")
    if sel:
        wanted = {int(s) for s in sel.split(",") if s.strip() != ""}
        devs = [d for d in devs if d.id in wanted]
    return devs
