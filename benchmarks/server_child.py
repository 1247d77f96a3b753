"""The server child: ``python -m llama_fastapi_k8s_gpu_tpu.server`` in
this process, with two things the program does not serve over HTTP written
to a file beside it: the device as JAX reports it (at start, so that a run
on the wrong platform ends before a model is loaded) and the peak device
memory (at exit).  Nothing of the program is imported or changed here; the
module runs as ``__main__`` exactly as ``-m`` would run it.

    python server_child.py <device.json>
"""

import atexit
import json
import os
import runpy
import sys


def _device_doc() -> dict:
    import jax

    devs = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devs]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(
            (int(s.get("peak_bytes_in_use", 0)) for s in stats), default=0),
        "memory_limit_bytes": max(
            (int(s.get("bytes_limit", 0)) for s in stats), default=0),
    }


def _write(path: str, at: str) -> None:
    doc = {"at": at, **_device_doc()}
    tmp = path + ".part"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


if __name__ == "__main__":
    out = sys.argv[1]
    import jax  # noqa: F401 -- first, so that this hook runs before JAX's own

    atexit.register(_write, out, "exit")
    _write(out, "start")
    sys.argv = ["llama_fastapi_k8s_gpu_tpu.server"]
    runpy.run_module("llama_fastapi_k8s_gpu_tpu.server", run_name="__main__",
                     alter_sys=True)
