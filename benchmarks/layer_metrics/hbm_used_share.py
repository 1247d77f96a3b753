"""kv ring: device memory in use at the window's end over the device's
limit (``/debug/memory``, reconciled by the program against
``memory_stats()``).  program_counter."""


def read(run):
    truth = (run["memory"] or {}).get("ground_truth") or {}
    if not truth.get("bytes") or not truth.get("limit"):
        return None
    return 100.0 * truth["bytes"] / truth["limit"]
