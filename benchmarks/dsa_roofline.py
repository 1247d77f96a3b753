"""What ``dsa_busy_share``, ``index_score_roofline`` and ``dsa_read_
roofline`` share (beside ``sala_roofline.py``: a helper of readers, no metric
of its own).  First the patterns that find a ``deepseek32`` file's sparse
attention in a capture (:func:`patterns`).  They are NOT a ``kernels/*.json``
group: ``run.py kernel_groups()`` hands every group to every cell and an
operation counts for the first group in name order that matches it, and a
bf16 shape that ends in (rows, 128) is also the scale plane of a fused matmul
whose N is that many rows (``mistral``'s ``wk``, ``olmoe``'s experts, ``sala``'s
feed-forward): a group named ``dsa`` would take those calls from ``qmatmul``
and ``expert_matmul`` in cells that have no indexer.  So the patterns stay
with the three readers that use them, are built from the cell's own
configuration, and take no custom call but the two kernels they name.

Then the two rooflines' arithmetic: the least time the bytes and FLOPs that two functions of the block count could take
(the larger of bytes over the chip's HBM bandwidth and FLOPs over its bf16
peak), over the device time of the decode step's operations that a pattern
finds.  Taken: those operations' self time as a share of the decode
programs' time in the capture (the ``XLA Modules`` line), times the median
decode step (``decode_step_roofline``'s clock).  Live lanes: the mean of
the scheduler's gauge over the window's samples; context: the records'
prompt + half the completion."""
import re

import costs
from ggufgen import block_of
from metrics import percentile
from opshare import _no_match
from server import parse_gauge
from spans import decode_chunks

#: models/mla.py ``INDEX_BLOCK``: the index keys a block of the indexer's
#: loop copies out of the leaf and scores (the whole leaf where it is shorter)
INDEX_BLOCK = 1024

#: the two latent kernels that take the selection (ops/pallas/attention.py)
SELECT_KERNELS = r"flash_attention_(?:decode|prefill)_latent_select"

# an XLA operation (fusion, copy, slice, update): no Mosaic call, whose int8
# planes' scales have such shapes too
_XLA = r"^(?!.*custom-call\()"


def leaf_pattern(cfg: dict, lead: str = r"(?:\d+,)*") -> str | None:
    """An XLA operation on the index-key leaf or a block of it: a bf16 shape
    whose last two dimensions are the leaf's (``serve.n_ctx`` positions of
    ``index_head_dim`` filled up to 128) or a block's, behind ``lead``.  None
    for a configuration without an indexer."""
    if not cfg.get("index_topk"):
        return None
    n_ctx = int(cfg["serve"]["n_ctx"])
    width = -(-int(cfg["index_head_dim"]) // 128) * 128
    rows = "|".join(str(n) for n in sorted({n_ctx, min(INDEX_BLOCK, n_ctx)}))
    return _XLA + r".*bf16\[%s(?:%s),%d\]" % (lead, rows, width)


def patterns(cfg: dict) -> list[str]:
    """What finds the sparse attention's own work in a capture of ``cfg``'s
    cell: the indexer (:func:`leaf_pattern`), the selection (the threshold
    search, ties and mask: XLA operations over rows of ``n_ctx`` scores, a
    u32, s32, pred or f32 shape whose LAST dimension is ``n_ctx``; the latent
    leaf's ``n_ctx`` is never last) and the selected read (the two kernels by
    name).  Empty for a configuration without an indexer."""
    leaf = leaf_pattern(cfg)
    if leaf is None:
        return []
    return [leaf,
            _XLA + r".*(?:u32|s32|pred|f32)\[(?:\d+,)*%d\]"
            % int(cfg["serve"]["n_ctx"]),
            SELECT_KERNELS]


def read(run, metric, op_pattern, bytes_fn_name, flops_fn_name):
    """None without a capture, on a block that brings neither function, or
    without a pattern (a configuration with no indexer); 0.0 (and
    ``notes.no_match``) where the capture holds no such operation."""
    p = run.get("profile")
    cfg = run["config"]
    block = block_of(cfg)
    bytes_fn = getattr(block, bytes_fn_name, None)
    flops_fn = getattr(block, flops_fn_name, None)
    if not p or bytes_fn is None or flops_fn is None or not op_pattern:
        return None
    progs = [re.compile(x)
             for x in run["kernel_groups"].get("decode_program", [])]
    durs = [d for n, _, d in p["modules"] if any(x.search(n) for x in progs)]
    steps = percentile([t for _, _, t in decode_chunks(run["traces"])], 50)
    if not durs or not steps:
        return None
    mine = re.compile(op_pattern)
    secs = sum(s for name, s in p["ops"].items() if mine.search(name))
    if not secs:
        _no_match(run, metric)
        return 0.0
    taken = percentile(durs, 50) / steps * secs / sum(durs)
    lanes = int(cfg["serve"]["env"].get("LFKT_BATCH_SIZE", 1))
    live = [parse_gauge(text, "scheduler_lanes_live")
            for _, text in run.get("samples") or []]
    live = [v for v in live if v is not None]
    n_live = sum(live) / len(live) if live else lanes
    ctx = [r.prompt_tokens + r.completion_tokens / 2 for r in run["records"]
           if r.prompt_tokens and r.completion_tokens]
    context = sum(ctx) / len(ctx) if ctx else 0
    nbytes = bytes_fn(cfg, n_live, context)
    flops = flops_fn(cfg, n_live, context)
    least, bound = costs.roofline_seconds(
        flops, nbytes, costs.peaks(run["device"]["kind"]))
    run["notes"][metric] = {
        "bound": bound, "least_ms": least * 1e3,
        "device_ms_per_step": taken * 1e3, "bytes_per_step": nbytes,
        "flops_per_step": flops, "lanes_live": n_live, "context": context}
    return 100.0 * least / taken


def lanes_of(run):
    return int(run["config"]["serve"]["env"].get("LFKT_BATCH_SIZE", 1))
