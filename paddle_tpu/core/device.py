"""Device / Place abstraction.

Mirrors the reference's Place variant (`paddle/fluid/platform/place.h`) and
`paddle.device.set_device` (`python/paddle/device.py:181`). On TPU there is a
single device kind per process; jax owns placement, we keep the user-facing API.
"""
import jax


class Place:
    def __init__(self, kind: str, device_id: int = 0):
        self.kind = kind
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.kind}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self.device_id == other.device_id
        )

    def is_tpu_place(self):
        return self.kind == "tpu"

    def is_cpu_place(self):
        return self.kind == "cpu"


def TPUPlace(device_id=0):
    return Place("tpu", device_id)


def CPUPlace():
    return Place("cpu", 0)


_current_device = None


def _default_kind():
    return jax.default_backend()


def set_device(device: str):
    """set_device('tpu') / set_device('tpu:0') / set_device('cpu')."""
    global _current_device
    kind, _, idx = device.partition(":")
    _current_device = Place(kind, int(idx) if idx else 0)
    return _current_device


def get_device() -> str:
    p = _current_place()
    return f"{p.kind}:{p.device_id}"


def _current_place() -> Place:
    global _current_device
    if _current_device is None:
        _current_device = Place(_default_kind(), 0)
    return _current_device


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def device_count() -> int:
    return jax.device_count()
