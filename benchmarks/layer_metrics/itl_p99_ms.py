"""scheduler: 99th percentile of all gaps between consecutive content
chunks of a stream, pooled over the window's requests, as the benchmark's
client saw them (``metrics.gaps_ms``): the freeze a reader sees when an
admission prefill or a queue stalls the lanes.  Not an end-to-end metric:
it sits on the edge between one and two admission slices inside the worst
gap (230 or 440 ms) and flips with the smallest change.  host_clock."""


def read(run):
    return run["e2e"].get("itl_p99_ms")
