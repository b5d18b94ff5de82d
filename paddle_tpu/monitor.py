"""Global stat monitor (reference: `paddle/fluid/platform/monitor.{h,cc}` —
StatRegistry monitor.h:77, STAT_ADD :130). Counters live in the native
runtime so C++ and Python components share one registry."""
from . import _native

_py_stats = {}


def stat_add(name, value=1):
    L = _native.lib()
    if L is not None:
        L.pt_stat_add(name.encode(), int(value))
    else:
        _py_stats[name] = _py_stats.get(name, 0) + int(value)


def stat_max(name, value):
    """Keep the largest value seen under ``name``: a worst case beside
    the sums (``to_static_call_max_ns``, ``host_gc_max_ns``). Listed by
    :func:`stats` like any counter; :func:`stat_reset` starts it over."""
    L = _native.lib()
    if L is not None:
        L.pt_stat_max(name.encode(), int(value))
    elif int(value) > _py_stats.get(name, 0):
        _py_stats[name] = int(value)


def stat_get(name):
    L = _native.lib()
    if L is not None:
        return int(L.pt_stat_get(name.encode()))
    return _py_stats.get(name, 0)


def stat_reset(name):
    L = _native.lib()
    if L is not None:
        L.pt_stat_reset(name.encode())
    else:
        _py_stats[name] = 0


def stats():
    """All counters as a dict."""
    import ctypes
    L = _native.lib()
    if L is None:
        return dict(_py_stats)
    buf = ctypes.create_string_buffer(1 << 16)
    n = L.pt_stat_list(buf, len(buf))
    text = buf.raw[: min(n, len(buf) - 1)].decode()
    if not text.endswith("\n"):  # truncated: drop the partial last name
        text = text[: text.rfind("\n") + 1]
    names = text.split()
    return {k: int(L.pt_stat_get(k.encode())) for k in names}
