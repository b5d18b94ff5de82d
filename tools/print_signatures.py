"""Print the public API surface as a stable spec (reference:
`tools/print_signatures.py` — generates paddle/fluid/API.spec, the frozen
API contract CI diffs against).

Usage:
    python tools/print_signatures.py             # print to stdout
    python tools/print_signatures.py --write     # refresh API.spec
"""
import argparse
import importlib
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODULES = [
    "paddle_tpu",
    "paddle_tpu.nn",
    "paddle_tpu.nn.functional",
    "paddle_tpu.nn.initializer",
    "paddle_tpu.ops",
    "paddle_tpu.optimizer",
    "paddle_tpu.optimizer.lr",
    "paddle_tpu.static",
    "paddle_tpu.jit",
    "paddle_tpu.jit.xla_flags",
    "paddle_tpu.analysis",
    "paddle_tpu.analysis.concurrency",
    "paddle_tpu.analysis.lockwatch",
    "paddle_tpu.analysis.shardcheck",
    "paddle_tpu.amp",
    "paddle_tpu.io",
    "paddle_tpu.metric",
    "paddle_tpu.linalg",
    "paddle_tpu.vision.models",
    "paddle_tpu.vision.transforms",
    "paddle_tpu.models",
    "paddle_tpu.distributed",
    "paddle_tpu.distributed.fleet",
    "paddle_tpu.distributed.pod",
    "paddle_tpu.distributed.ps",
    "paddle_tpu.quantization",
    "paddle_tpu.sparsity",
    "paddle_tpu.inference",
    "paddle_tpu.observability",
    "paddle_tpu.observability.memory",
    "paddle_tpu.observability.overlap",
    "paddle_tpu.recompute",
    "paddle_tpu.serving",
    "paddle_tpu.checkpoint",
    "paddle_tpu.checkpoint.multihost",
    "paddle_tpu.testing",
    "paddle_tpu.testing.faults",
    "paddle_tpu.testing.virtual_pod",
    "paddle_tpu.onnx",
    "paddle_tpu.incubate",
    "paddle_tpu.text",
    "paddle_tpu.hapi",
]

SPEC_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "API.spec")


def _sig_of(obj):
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        sig = "(...)"
    return sig


def _is_ours(obj):
    """Freeze this repo's names, not what it re-exports from jax or
    numpy: their signatures and reprs move with every upgrade."""
    mod = getattr(obj, "__module__", None) or ""
    return mod == "paddle_tpu" or mod.startswith("paddle_tpu.")


def collect():
    lines = []
    for modname in MODULES:
        try:
            mod = importlib.import_module(modname)
        except ImportError as e:
            lines.append(f"{modname} IMPORT-ERROR {e}")
            continue
        names = getattr(mod, "__all__", None)
        if names is None:
            names = [n for n in dir(mod) if not n.startswith("_")]
        for name in sorted(set(names)):
            obj = getattr(mod, name, None)
            if obj is None or inspect.ismodule(obj) or not _is_ours(obj):
                continue
            if inspect.isclass(obj):
                lines.append(f"{modname}.{name} class{_sig_of(obj)}")
                for mname in sorted(dir(obj)):
                    if mname.startswith("_"):
                        continue
                    raw = inspect.getattr_static(obj, mname, None)
                    # getattr_static sees class/static/plain methods alike
                    # (callable(classmethod) is False; vars() misses
                    # inherited methods) — properties freeze as attributes
                    if isinstance(raw, (classmethod, staticmethod)):
                        meth = raw.__func__
                        kind = ("classmethod"
                                if isinstance(raw, classmethod)
                                else "staticmethod")
                    elif inspect.isfunction(raw):
                        meth, kind = raw, "method"
                    elif isinstance(raw, property):
                        lines.append(
                            f"{modname}.{name}.{mname} property")
                        continue
                    else:
                        continue
                    lines.append(
                        f"{modname}.{name}.{mname} {kind}{_sig_of(meth)}")
            elif callable(obj):
                lines.append(f"{modname}.{name} function{_sig_of(obj)}")
    return sorted(set(lines))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--write", action="store_true",
                    help="refresh API.spec in place")
    args = ap.parse_args()
    lines = collect()
    text = "\n".join(lines) + "\n"
    if args.write:
        with open(SPEC_PATH, "w") as f:
            f.write(text)
        print(f"wrote {len(lines)} entries to {SPEC_PATH}")
    else:
        sys.stdout.write(text)


if __name__ == "__main__":
    main()
