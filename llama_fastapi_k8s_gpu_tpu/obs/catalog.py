"""THE metric catalog — every series /metrics may expose, declared once.

Same single-source-of-truth pattern as the ``LFKT_*`` knob registry
(utils/config.py): every metric name the package passes to
``Metrics.inc/observe/set_gauge`` must be declared here with its type,
help text and (for histograms) buckets.  The registry is enforced at
runtime (an unregistered name raises ``KeyError``, utils/metrics.py) and
statically (lfkt-lint OBS001, lint/obsreg.py); the docs table in
docs/OBSERVABILITY.md is GENERATED from this module (``python -m
llama_fastapi_k8s_gpu_tpu.obs.catalog``) and pinned by OBS002 + a tier-1
test, so a typo'd metric name or an undocumented metric fails the gate.

Engines that synthesize families at runtime (the continuous scheduler's
``scheduler_stats()`` dict) declare a *prefix family* instead of one entry
per key — the ``scheduler_`` entry below — mirroring the bench-only knob
allowlist in lint/configreg.py.
"""

from __future__ import annotations

import dataclasses

COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"

#: default latency buckets (seconds): tuned for a serving path whose TTFT
#: sits in the 0.05-1 s band and whose tail is the 25 s admission timeout
LATENCY_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                   5.0, 10.0, 25.0, 60.0)
#: decode throughput buckets (tokens/sec)
RATE_BUCKETS = (1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0)
#: token-count buckets (prefix reuse lengths: one page up to a 32k prompt)
TOKEN_BUCKETS = (64.0, 128.0, 256.0, 512.0, 1024.0, 2048.0, 4096.0,
                 8192.0, 16384.0, 32768.0)
#: compile-wall buckets (seconds): CPU-tiny test programs compile in tens
#: of ms, real 8B prefill programs in tens of seconds on a cold cache
COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                   30.0, 60.0, 120.0)


@dataclasses.dataclass(frozen=True)
class Metric:
    """One registered metric family.  ``labels`` names the allowed label
    keys (order is the render order); ``prefix=True`` registers a family
    of runtime-synthesized gauges sharing the name as a prefix."""

    name: str
    mtype: str = COUNTER
    help: str = ""
    buckets: tuple = ()
    labels: tuple = ()
    prefix: bool = False


def _register(*metrics: Metric) -> dict[str, Metric]:
    out: dict[str, Metric] = {}
    for m in metrics:
        if m.mtype == HISTOGRAM and not m.buckets:
            raise ValueError(f"histogram {m.name} needs explicit buckets")
        out[m.name] = m
    return out


METRICS: dict[str, Metric] = _register(
    # -- request path (server/app.py) --------------------------------------
    Metric("http_requests_total", COUNTER,
           "requests served, by route and status code",
           labels=("route", "code")),
    Metric("request_seconds", HISTOGRAM,
           "end-to-end request latency, by route",
           buckets=LATENCY_BUCKETS, labels=("route",)),
    Metric("queue_wait_seconds", HISTOGRAM,
           "admission-queue wait (enqueue -> consumer pickup)",
           buckets=LATENCY_BUCKETS),
    Metric("generation_seconds", HISTOGRAM,
           "engine generation wall time (prefill + decode), by model",
           buckets=LATENCY_BUCKETS, labels=("model",)),
    Metric("queue_depth", GAUGE, "admission queue occupancy"),
    Metric("requests_rejected_total", COUNTER,
           "503s from the bounded admission queue"),
    Metric("requests_timed_out_total", COUNTER,
           "408s (admission timeout / stream deadline)"),
    # -- engine phase timings (SURVEY §5 per-phase timers) -----------------
    Metric("engine_ttft_seconds", HISTOGRAM,
           "time to first token (prefill + first sample), by prefill "
           "bucket and model — the SLO engine evaluates each label "
           "series separately, so burn rates report the worst "
           "bucket+model (docs/SLO.md)",
           buckets=LATENCY_BUCKETS, labels=("bucket", "model")),
    Metric("engine_decode_tokens_per_sec", HISTOGRAM,
           "per-request decode throughput, by model",
           buckets=RATE_BUCKETS, labels=("model",)),
    Metric("generated_tokens_total", COUNTER, "completion tokens emitted"),
    Metric("streamed_generations_total", COUNTER, "SSE streams served"),
    # -- prefix reuse ------------------------------------------------------
    Metric("prefix_cache_hits_total", COUNTER,
           "requests served with prompt-prefix KV reuse"),
    Metric("prefix_cache_reused_tokens_total", COUNTER,
           "prompt tokens NOT re-prefilled thanks to prefix reuse"),
    # -- block-paged KV pool + radix prefix cache (parallel/kvpool.py) -----
    Metric("prefix_cache_misses_total", COUNTER,
           "requests that consulted the radix prefix index and took a "
           "full prefill (no usable cached prefix)"),
    Metric("prefix_cache_evictions_total", COUNTER,
           "KV pool nodes evicted (LRU, unpinned) to free pages"),
    Metric("prefix_cache_spills_total", COUNTER,
           "evicted KV nodes DMA'd to the host-RAM spill tier"),
    Metric("prefix_cache_restores_total", COUNTER,
           "spilled KV nodes restored to HBM on a prefix hit"),
    Metric("prefix_reuse_tokens", HISTOGRAM,
           "per-hit prompt tokens served from cached KV pages",
           buckets=TOKEN_BUCKETS),
    Metric("kv_pool_pages_used", GAUGE,
           "KV pool pages holding indexed cache content"),
    Metric("kv_pool_pages_free", GAUGE, "KV pool pages on the free list"),
    # -- disaggregated prefill/decode (serving/disagg/) --------------------
    Metric("disagg_prefills_served_total", COUNTER,
           "prefill tier: remote prefill requests answered with pages"),
    Metric("disagg_pages_sent_total", COUNTER,
           "prefill tier: KV pages streamed to decode replicas"),
    Metric("disagg_bytes_sent_total", COUNTER,
           "prefill tier: page payload bytes put on the wire"),
    Metric("disagg_remote_prefills_total", COUNTER,
           "decode replica: admissions whose prefix was imported from "
           "the prefill tier (pages restored instead of local prefill)"),
    Metric("disagg_pages_received_total", COUNTER,
           "decode replica: KV pages received from the prefill tier"),
    Metric("disagg_bytes_received_total", COUNTER,
           "decode replica: page payload bytes received"),
    Metric("disagg_local_fallbacks_total", COUNTER,
           "decode replica: remote prefills degraded to LOCAL prefill, "
           "by reason (peer_dead, peer_unreachable, refused, import, "
           "prefill, ...) — nonzero = the split fleet is not splitting",
           labels=("reason",)),
    Metric("disagg_handshake_refusals_total", COUNTER,
           "page-wire handshakes refused (schema/geometry mismatch — "
           "a mis-deployed tier pair, docs/RUNBOOK.md)"),
    Metric("disagg_transfer_seconds", HISTOGRAM,
           "decode replica: one remote-prefill hop's wall (request -> "
           "pages imported)",
           buckets=LATENCY_BUCKETS),
    Metric("disagg_peer_connected", GAUGE,
           "decode replica: 1 while the prefill peer connection is up"),
    # -- fleet tier: the prefix-affinity router (serving/fleet/) -----------
    Metric("fleet_requests_total", COUNTER,
           "router: requests proxied, by serving replica and affinity-"
           "key source (header | conversation | prefix | opaque)",
           labels=("peer", "source")),
    Metric("fleet_spills_total", COUNTER,
           "router: requests NOT served by their rendezvous owner, by "
           "reason (ejected = retried onto the next peer, spilled = "
           "served off-owner, mid_stream_abort = peer died after bytes "
           "reached the client, no_replica = whole fleet down -> 503); "
           "sustained nonzero = conversations are running cold",
           labels=("reason",)),
    Metric("fleet_peer_ejections_total", COUNTER,
           "router: replica ejections (probe failure or proxied-request "
           "failure), by peer — the /health peers block names the reason",
           labels=("peer",)),
    Metric("fleet_peers_healthy", GAUGE,
           "router: replicas currently accepting traffic"),
    Metric("fleet_proxy_seconds", HISTOGRAM,
           "router: one proxied request's wall (client head in -> "
           "backend response relayed)",
           buckets=LATENCY_BUCKETS),
    Metric("fleet_probe_seconds", HISTOGRAM,
           "router: one health-probe round trip per replica, success or "
           "failure — the ejection-threshold tuning signal (a peer whose "
           "probes crawl toward the timeout is about to be ejected)",
           buckets=LATENCY_BUCKETS, labels=("peer",)),
    # -- fleet KV migration (serving/fleet/migrate.py) ---------------------
    Metric("kv_migration_pulls_total", COUNTER,
           "migration pulls attempted, by trigger (remap = router "
           "prior-owner hint, warmup = scale-out pre-pull, drain = "
           "commanded pull from a DRAINING peer)",
           labels=("reason",)),
    Metric("kv_migration_pushes_total", COUNTER,
           "migration page service: pull requests answered with pages "
           "(this pod was the warm side)"),
    Metric("kv_migration_pages_total", COUNTER,
           "KV pages moved by migration, by direction (pulled | pushed)",
           labels=("reason",)),
    Metric("kv_migration_failures_total", COUNTER,
           "migration attempts degraded, by reason (connect, wire, "
           "refused, deadline, import, drain_push, ...) — every one "
           "fell back to local recompute or plain termination, with "
           "this attribution",
           labels=("reason",)),
    Metric("kv_migration_seconds", HISTOGRAM,
           "one migration hop's wall (request -> pages imported)",
           buckets=LATENCY_BUCKETS),
    # -- live manifest reload (serving/registry.py reload_manifest) --------
    Metric("model_reloads_total", COUNTER,
           "live-reload actions on the model registry (add = model "
           "loaded+warmed in place, remove = namespace drained + weights "
           "released, refused = budget/fit/grammar refusal with the "
           "running set untouched)",
           labels=("action",)),
    # -- prefill pipeline (overlapped chunked prefill + admission control) --
    Metric("prefill_slice_seconds", HISTOGRAM,
           "host wall of one prefill-slice dispatch (prep + enqueue; "
           "long = device-queue pushback)",
           buckets=LATENCY_BUCKETS),
    Metric("admission_budget_tokens", GAUGE,
           "admission controller's live per-wave prefill-token budget"),
    Metric("lane_idle_seconds", GAUGE,
           "cumulative idle lane-seconds while other lanes decoded "
           "(monotonic; the admission controller's raw loss signal)"),
    # -- resilience / error classes (docs/RUNBOOK.md) ---------------------
    Metric("engine_unavailable_total", COUNTER,
           "503s from watchdog trips / recovery in progress"),
    Metric("engine_errors_total", COUNTER, "engine-side request failures"),
    Metric("watchdog_trips_total", COUNTER, "watchdog trip count"),
    Metric("watchdog_recoveries_total", COUNTER,
           "successful watchdog recoveries"),
    Metric("watchdog_escalations_total", COUNTER,
           "recovery budget exhaustions (DEAD)"),
    Metric("health_state", GAUGE,
           "pod health state code (0=STARTING 1=READY 2=DEGRADED "
           "3=DRAINING 4=DEAD)"),
    Metric("engine_inflight", GAUGE, "engine busy count (heartbeat)"),
    Metric("engine_error_count", GAUGE, "heartbeat errors_total"),
    # -- capacity ----------------------------------------------------------
    Metric("kv_cache_bytes", GAUGE, "resident KV-cache HBM bytes"),
    # -- lfkt-mem: live HBM memory ledger (obs/memledger.py) ---------------
    Metric("hbm_bytes", GAUGE,
           "live HBM bytes per memory-ledger component and model "
           "(component=residual carries bytes the ledger cannot "
           "attribute vs device ground truth; docs/OBSERVABILITY.md "
           "memory-ledger section)",
           labels=("component", "model")),
    Metric("hbm_headroom_bytes", GAUGE,
           "free device HBM (bytes_limit - bytes_in_use); only exported "
           "where the backend reports memory_stats"),
    Metric("mem_pressure_events_total", COUNTER,
           "admission-controller budget cuts triggered by low HBM "
           "headroom (rising edges, not waves — docs/RUNBOOK.md "
           "'Diagnosing HBM OOM')"),
    # -- lfkt-mem: incident flight recorder (obs/flightrec.py) -------------
    Metric("incidents_total", GAUGE,
           "incident bundles recorded by the flight recorder this "
           "process (snapshot; bundles live in LFKT_INCIDENT_DIR)"),
    # -- multi-tenant token metering (server/app.py usage counts) ----------
    Metric("tokens_prompt_total", COUNTER,
           "prompt tokens ingested, by model (from the engines' own "
           "usage counts — metering without scraping /v1 responses)",
           labels=("model",)),
    Metric("tokens_generated_total", COUNTER,
           "completion tokens emitted, by model (from the engines' own "
           "usage counts)",
           labels=("model",)),
    # -- multi-model serving (serving/registry.py; docs/MULTIMODEL.md) -----
    Metric("models_loaded", GAUGE,
           "models served by this process (manifest rows, or 1)"),
    Metric("model_weight_bytes", GAUGE,
           "resident weight HBM bytes per served model (the registry's "
           "LFKT_HBM_WEIGHT_BUDGET_MB accounting unit)",
           labels=("model",)),
    # -- tracer self-telemetry (obs/trace.py) ------------------------------
    Metric("trace_ring_used", GAUGE, "completed traces held in the ring"),
    # monotonic tracer counters exported as point-in-time snapshots (the
    # tracer owns the count; /metrics copies it rather than re-counting)
    Metric("traces_started_total", GAUGE, "requests that drew a trace"),
    Metric("traces_sampled_out_total", GAUGE,
           "requests skipped by LFKT_TRACE_SAMPLE"),
    # -- lfkt-perf: compile/dispatch attribution (obs/devtime.py) ----------
    # per-program counters exported as point-in-time snapshots — the
    # devtime registry owns the count; /metrics copies it (same convention
    # as the tracer counters above)
    Metric("xla_compiles_total", GAUGE,
           "jit compile events per program: calls that grew the jit cache "
           "and handed a lowering to the compiler (devtime snapshot)",
           labels=("program",)),
    Metric("jit_dispatches_total", GAUGE,
           "host dispatches per jit program (devtime snapshot)",
           labels=("program",)),
    Metric("jit_device_seconds_total", GAUGE,
           "device seconds per jit program while the tracer is armed: the "
           "sum of its intervals [max(previous done, its dispatch's "
           "return), done], done stamped when one leaf of its result is "
           "ready.  An UPPER bound: eager device work, transfers and "
           "programs without a stamp lie in the next stamped interval "
           "(devtime snapshot)",
           labels=("program",)),
    Metric("jit_device_intervals_total", GAUGE,
           "device intervals summed into jit_device_seconds_total per "
           "program: its stamped dispatches (devtime snapshot)",
           labels=("program",)),
    Metric("executables_loaded_total", GAUGE,
           "first calls of a signature whose executable was loaded from "
           "the executable store, no trace and no lowering "
           "(utils/execstore.py; devtime snapshot)", labels=("program",)),
    Metric("executables_built_total", GAUGE,
           "first calls of a signature that built the executable and "
           "wrote it to the executable store (devtime snapshot)",
           labels=("program",)),
    Metric("executable_load_seconds_total", GAUGE,
           "wall of the first calls counted in executables_loaded_total: "
           "the file's read, the load, the first dispatch "
           "(devtime snapshot)", labels=("program",)),
    Metric("executable_build_seconds_total", GAUGE,
           "wall of the first calls counted in executables_built_total: "
           "trace, lowering, compile or persistent-cache read, "
           "serialisation, the first dispatch (devtime snapshot)",
           labels=("program",)),
    Metric("executable_load_failures_total", GAUGE,
           "executable store files that did not load (truncated, another "
           "runtime, a changed pickle): each deleted and its program "
           "built again"),
    Metric("xla_recompile_storms_total", GAUGE,
           "signatures minted past LFKT_RECOMPILE_BUDGET "
           "(devtime snapshot; docs/RUNBOOK.md recompile-storm runbook)"),
    Metric("xla_compile_seconds", HISTOGRAM,
           "wall time of jit compile events, by program (first-dispatch "
           "wall; replayed from the devtime event ring at scrape time)",
           buckets=COMPILE_BUCKETS, labels=("program",)),
    Metric("xla_compile_events_dropped_total", GAUGE,
           "compile events evicted from the ring before replay — nonzero "
           "means xla_compile_seconds undercounts vs xla_compiles_total "
           "(a storm outran the scrape cadence)"),
    # -- SLO engine (obs/slo.py; docs/SLO.md) ------------------------------
    Metric("slo_burn_rate", GAUGE,
           "error-budget burn rate per SLO and window (1.0 = burning "
           "exactly the budget; sustained >1 on every window = breach); "
           "scope=pod on replica scrapes, scope=fleet when the router "
           "evaluates the catalog over federated histograms",
           labels=("slo", "window", "scope")),
    Metric("weight_fill_share", GAUGE,
           "per cent of the resident fused weight planes' bytes that are "
           "zero fill: a K the kernels' 2048 tile does not divide and that "
           "ends in no tail tile is filled up with zero blocks, which every "
           "step reads (ops/linear.py padded_k, ops/pallas/experts.py "
           "padded_k); the loader's own sum over the planes it prepared, "
           "as /health engine.weight_fill_share; absent where no plane is "
           "fused"),
    # -- routed layers (engine/expert_counters.py; a file with experts) ----
    Metric("expert_layer_steps_total", GAUGE,
           "routed feed-forward layers run by decode chunks: (layer, step) "
           "pairs, cumulative; counted on the device, folded at scrape"),
    Metric("experts_read_total", GAUGE,
           "distinct experts whose weights a decode step's live lanes made "
           "the grouped matmuls read, summed over the (layer, step) pairs; "
           "over expert_layer_steps_total = experts read per layer-step"),
    Metric("expert_slots_skipped_total", GAUGE,
           "slots of the decode steps' grouped expert calls that held no "
           "expert and were never walked (the call's grid ends at the "
           "slots in use): expert_layer_steps_total x the slots of a call "
           "(/health engine.expert_slots: the held experts or a step's "
           "(token, pick) rows, the fewer) - experts_read_total; host "
           "arithmetic at scrape; 0 where the experts serve dequantized"),
    Metric("expert_rows_skipped_total", GAUGE,
           "(token, pick) rows the decode steps offered their grouped "
           "expert calls that reached no expert held here and were never "
           "multiplied (a layer of more than 64 rows sends the rows that "
           "reach an expert through calls of 64): expert_layer_steps_total "
           "x the rows of a step's layer (/health engine.expert_rows) - "
           "expert_picks_held_total; host arithmetic at scrape; 0 where a "
           "step's layer has 64 rows or fewer (it is built as it always "
           "was) or the experts serve dequantized"),
    Metric("expert_picks_total", GAUGE,
           "(token, pick) rows each expert took in decode chunks, "
           "cumulative; the largest over their sum is the most-loaded "
           "expert's share (over the experts HELD here, numbered from "
           "the first held)", labels=("expert",)),
    Metric("expert_picks_routed_total", GAUGE,
           "picks the decode chunks' routers made for live rows over ALL "
           "the router's experts, cumulative (picks_total)"),
    Metric("expert_picks_held_total", GAUGE,
           "of those, the picks of an expert held here (picks_held): the "
           "rest left the chip in the deployment the file stands for; "
           "equal to expert_picks_routed_total where every expert is held"),
    Metric("expert_picks_zero_total", GAUGE,
           "of expert_picks_routed_total, the picks of a zero expert "
           "(picks_zero: a router output that is no expert, the identity of "
           "a longcat-flash file, computed where the token lives at no "
           "weight read); 0 where the router has none; zero + held <= "
           "routed, and routed - zero are the picks of a real expert"),
    # -- decode attention's read of the KV ring (models/llama.py) ----------
    Metric("ring_slots_read_total", GAUGE,
           "KV ring slots the decode steps' attention covered (whole blocks "
           "up to the position: under the decode kernel each lane's own, "
           "under the XLA loop of a lane engine up to the largest LIVE "
           "lane's position, one bound for all lanes; models/llama.py "
           "decode_kernel_block), summed over decode steps and over the "
           "lanes that hold a request, cumulative; from host-tracked "
           "positions (a lane engine adds at each chunk's harvest), nothing "
           "fetched"),
    Metric("ring_slots_live_total", GAUGE,
           "KV ring slots at or below the sequence's own position, summed "
           "over the same steps and lanes; over ring_slots_read_total = the "
           "share of the read that was needed (a whole-ring read of 4096 "
           "at chat lengths: 0.11)"),
    Metric("ring_rows_written_total", GAUGE,
           "K rows (and as many V rows) the decode kernel stored in the KV "
           "ring: lanes a decode chunk was dispatched with as live x its "
           "steps x the layers, cumulative; a lane that holds no request "
           "stores nothing.  0 where XLA writes the row (/health "
           "engine.ring_write = xla: int8 rings, the CPU, a state "
           "+ ring cache); host arithmetic at the chunk's harvest, nothing "
           "fetched"),
    # -- layer applications (models/llama.py layer_passes; a ring) ----------
    Metric("layer_passes_total", GAUGE,
           "layer applications the programs ran, by phase: decode = lanes "
           "whose rows were wanted x the chunk's steps x the bodies of "
           "forward's one loop (n_layers, x ut_steps where layers run "
           "several times: 192 at 48 x 4), prefill = tokens of the "
           "dispatched slices x the same; cumulative, host arithmetic at "
           "the chunk's harvest and the slice's dispatch, nothing fetched; "
           "exported by a file whose cache is the ring",
           labels=("phase",)),
    Metric("decode_lane_steps_total", GAUGE,
           "decode steps summed over the lanes whose rows were wanted (a "
           "serial engine: its one sequence), cumulative: what "
           "layer_passes_total{phase=\"decode\"} is divided by"),
    Metric("ut_exit_mass_total", GAUGE,
           "a looped stack's exit gate (ut_steps > 1): the exit rule's mass "
           "p_t = lam_t prod_{j<t}(1 - lam_j) of pass t (the last pass "
           "takes what is left), summed over the decoded tokens of the "
           "lanes alive in their step; the passes' sum is the tokens "
           "decoded.  Computed in the decode programs (forward with_stats) "
           "and folded at a scrape from the chunks that have finished "
           "(engine/expert_counters.py ExitMass); at the served threshold "
           "1.0 it moves no token: what an exit rule under 1.0 would have "
           "had to work with",
           labels=("pass",)),
    # -- how a prompt was cut into prefill slices (engine/slices.py) --------
    Metric("prefill_slice_tokens_total", GAUGE,
           "prompt tokens prefilled (padding included), cumulative, by the "
           "width of the program that took them: wide = more than the narrow "
           "width (LFKT_PREFILL_CHUNK), which the plan cuts only where nobody "
           "decodes behind the slice (the serial engine always; the lane "
           "engine while no lane holds a request and no chunk is in flight); "
           "over both widths = the share of the prompt that paid one weight "
           "pass per wide slice and not per narrow one; host arithmetic at "
           "each dispatch", labels=("width",)),
    # -- the tokenizer's memo of pieces (tokenizer/spm.py) -------------------
    Metric("tokenizer_pieces_total", GAUGE,
           "pieces of escaped text (a run of spaces and the word after it) "
           "a SentencePiece tokenizer encoded, over every thread, "
           "cumulative; 0 for a vocabulary that cannot be cut at spaces "
           "(/health engine.tokenizer says so), absent for a tokenizer of "
           "another family"),
    Metric("tokenizer_memo_hits_total", GAUGE,
           "of those, the pieces whose ids came from the memo and not from "
           "the merge loop; over tokenizer_pieces_total = the hit share"),
    # -- the window + summary cache's read (models/eva.py; ``evabyte``) -----
    Metric("eva_lane_steps_total", GAUGE,
           "decode steps summed over the lanes that hold a request (a "
           "serial engine: its steps): what the eva_* sums below are over, "
           "so that their ratio to it is entries per lane and step"),
    Metric("eva_window_slots_read_total", GAUGE,
           "window slots the decode steps' attention covered (whole blocks "
           "up to the fill, position mod window; on a lane engine up to "
           "the largest LIVE lane's fill; models/eva.py decode_attention), "
           "summed over decode steps and over the lanes that hold a "
           "request, cumulative; from host-tracked positions, nothing "
           "fetched; exported by a file of that cache kind only"),
    Metric("eva_window_slots_live_total", GAUGE,
           "window slots at or below the sequence's own fill, over the "
           "same steps and lanes"),
    Metric("eva_summaries_read_total", GAUGE,
           "chunk summaries the decode steps' attention covered (one "
           "closed window's at a time, up to the live lane with most "
           "closed windows), over the same steps and lanes"),
    Metric("eva_summaries_live_total", GAUGE,
           "chunk summaries of the windows before the sequence's own, over "
           "the same steps and lanes; (window + summaries) live over read "
           "= the share of the read that was needed"),
    Metric("eva_windows_closed_total", GAUGE,
           "windows turned into their chunk summaries: whole windows of a "
           "prompt at its prefill, and a decode step that writes a "
           "window's last position; cumulative"),
    # -- the window + global cache (models/hybrid.py; ``exaone-moe``) --------
    Metric("window_slots_read_total", GAUGE,
           "slots of the WINDOW layers' cache leaves the decode steps' "
           "attention covered: the whole leaf (the window, filled up to 16 "
           "rows) a step, lane that holds a request and window layer, "
           "cumulative; in layer-slots, so that it adds to "
           "global_slots_read_total (ring_slots_read_total is their sum); "
           "from host-tracked positions, nothing fetched; exported by a "
           "file of that cache kind only"),
    Metric("window_slots_live_total", GAUGE,
           "of those, the slots that held a position inside the window "
           "(min(position + 1, window) a step, lane and window layer)"),
    Metric("global_slots_read_total", GAUGE,
           "slots of the GLOBAL layers' rings the decode steps' attention "
           "covered (whole blocks up to the position, as "
           "ring_slots_read_total counts a ring), summed over the global "
           "layers too, cumulative"),
    Metric("global_slots_live_total", GAUGE,
           "of those, the slots at or below the sequence's own position; "
           "(window + global live) over (window + global read) = the share "
           "of the read that was needed"),
    # -- the conv-state + ring cache (models/lfm2.py; ``lfm2moe``) ----------
    Metric("conv_state_updates_total", GAUGE,
           "updates of a conv layer's carried rows (the last l_cache - 1 "
           "inputs of its taps) in decode steps: one per step, conv layer "
           "and lane that holds a request (a serial engine: its one "
           "sequence), cumulative; a lane that holds none runs the step's "
           "arithmetic and keeps its rows; from host-tracked positions, "
           "nothing fetched; exported by a file of that cache kind only "
           "(its ring_slots_* are summed over the attention layers)"),
    Metric("conv_state_starts_total", GAUGE,
           "prefills that began from zero rows: every prompt's, since the "
           "carried rows cannot be rolled back to a prefix; cumulative"),
    # -- the state + window + shared-ring cache (models/phi4flash.py) -------
    # (its window_slots_* are the window + global cache's families above)
    Metric("ssm_state_updates_total", GAUGE,
           "selective-scan states stepped in decode steps for a lane that "
           "holds an unfinished request: one per step, state-space layer "
           "and such lane (a serial engine: its one sequence), cumulative; "
           "from host-tracked positions, nothing fetched; exported by a "
           "file of that cache kind only"),
    Metric("ssm_state_steps_total", GAUGE,
           "selective-scan states the decode steps' arithmetic stepped: "
           "one per step, state-space layer and LANE OF THE BATCH, whether "
           "it holds a request or not (a lane that holds none keeps its "
           "state); ssm_state_updates_total over it = the mean share of "
           "live lanes"),
    Metric("ssm_state_starts_total", GAUGE,
           "prefills that began from zero states: every prompt's, since a "
           "state cannot be rolled back to a prefix; cumulative"),
    Metric("shared_leaf_reads_total", GAUGE,
           "reads of the ONE shared K/V leaf (the full-attention layer's) "
           "by decode steps: one per step, lane with an unfinished request "
           "and READING layer (the full layer and every cross layer), "
           "cumulative"),
    Metric("shared_leaf_steps_total", GAUGE,
           "decode steps summed over the lanes with an unfinished request: "
           "shared_leaf_reads_total over it = reads of the leaf a step"),
    Metric("shared_leaf_slots_read_total", GAUGE,
           "slots of the shared leaf those reads covered (whole blocks up "
           "to the lane's position under the decode kernel, up to the "
           "largest live lane's under the XLA loop), summed over the "
           "reading layers; ring_slots_read_total is this plus "
           "window_slots_read_total"),
    Metric("shared_leaf_slots_live_total", GAUGE,
           "of those, the slots at or below the sequence's own position"),
    Metric("prefill_layer_rows_run_total", GAUGE,
           "(layer, prompt row) pairs the prefill programs ran, padding "
           "included, cumulative: a slice that holds no prompt's last token "
           "runs the layers up to the full-attention one, the slice that "
           "does runs the others on that one row; host arithmetic at each "
           "dispatch"),
    Metric("prefill_layer_rows_skipped_total", GAUGE,
           "(layer, prompt row) pairs no program ran: the layers above the "
           "full-attention one write no cache, so no later token reads them "
           "at a prompt position; over this plus "
           "prefill_layer_rows_run_total = the share of the stack's "
           "applications a prompt did not pay"),
    Metric("prefill_programs_total", GAUGE,
           "prefill programs dispatched, by the part of the stack they run: "
           "lower = up to the full-attention layer (a slice that holds no "
           "prompt's last token), whole = every layer", labels=("stack",)),
    # -- the ssm-state + ring cache (models/jamba.py; ``jamba``) -------------
    # (its ssm_state_* are the three above; its ring_slots_* are summed over
    # the attention layers)
    Metric("prefill_ring_blocks_live_total", GAUGE,
           "fused key blocks the prefill attention kernel's calls NEEDED: "
           "those up to each slice's last row's position, once a row tile "
           "and attention layer, summed over a prompt's slices at its "
           "admission (host arithmetic on the slice plan and the kernel's "
           "static block sizes: ops/pallas/attention.py flash_plan), "
           "cumulative; 0 where the slices' attention is no kernel; exported "
           "by a file of that cache kind only"),
    Metric("prefill_ring_blocks_walked_total", GAUGE,
           "fused key blocks those calls' grids WALKED: the same where the "
           "key axis ends at the slice's end (a ring of more than "
           "WALK_WHOLE_STEPS fused blocks), the whole ring's where it is "
           "walked whole; live over walked = the share of the walk a slice "
           "needed"),
    # -- the latent ring (models/mla.py; ``deepseek2``) ----------------------
    Metric("latent_positions_read_total", GAUGE,
           "cached latent rows the decode steps' attention covered (whole "
           "blocks: under the decode kernel each lane's own, up to its "
           "position; under the XLA loop up to the largest LIVE lane's "
           "position; summed over live lanes and steps; a row is read once "
           "for all heads), cumulative"),
    Metric("latent_positions_live_total", GAUGE,
           "cached latent rows at or below the decoding positions in the "
           "same steps (what the attention needed); over "
           "latent_positions_read_total = the share of the read that was "
           "live"),
    Metric("latent_slices_kernel_total", GAUGE,
           "prefill programs (slices) whose attention over the cached "
           "latents ran as the slice kernel (ops/pallas/attention.py "
           "latent_attention_prefill: the scratch leaf in place, the "
           "scores in VMEM; /health engine.latent_slice_read = kernel: a "
           "TPU whose probe passed, a slice width that a tile fits), "
           "counted at dispatch, cumulative"),
    Metric("latent_slices_loop_total", GAUGE,
           "prefill programs whose attention over the cached latents ran "
           "as the plain XLA loop over blocks of 512 (models/mla.py "
           "latent_attention: the CPU, a failed probe, a width no tile "
           "fits); beside latent_slices_kernel_total the kernel's "
           "engagement"),
    # -- the latent ring's index-key leaf (models/mla.py; ``deepseek32``) -----
    Metric("index_keys_scored_total", GAUGE,
           "cached index keys the learned indexer scored, summed over "
           "queries and layers: every position at or below the query "
           "(label phase: prefill = a prompt's rows from the reused prefix "
           "on, decode = the live lanes' steps); host arithmetic from "
           "tracked positions, nothing fetched; exported by a deepseek32 "
           "file only, cumulative", labels=("phase",)),
    Metric("latents_selected_total", GAUGE,
           "cached latent rows the selection chose, summed over queries and "
           "layers: min(index_topk, position + 1) a query (label phase); "
           "over index_keys_scored_total = the share of the live positions "
           "a query attends.  Like it, what the ALGORITHM prescribes at the "
           "tracked positions (host arithmetic), not a count taken on the "
           "device: a property of the traffic's contexts under index_topk, "
           "so of the two shares built on it only the one over "
           "latents_read_total can move with the program, and only when "
           "the READ changes", labels=("phase",)),
    Metric("latents_read_total", GAUGE,
           "cached latent rows the attention FETCHED for those queries, "
           "whichever read served (label phase): the selection is a MASK "
           "on the blocks the read walks, so a decode step fetches its "
           "lane's whole blocks up to its position (the bound's under the "
           "XLA loop) and a slice's every row the blocks up to the slice's "
           "end; latents_selected_total over it = how sparse the read is "
           "in fact (100 % would be a read of the selected rows alone)",
           labels=("phase",)),
    # -- the state + ring cache (models/sala.py; ``minicpm-sala``) ----------
    Metric("lin_state_updates_total", GAUGE,
           "updates of a linear-attention layer's state in decode steps: "
           "one per step, linear layer and lane that holds a request (a "
           "serial engine: its one sequence), cumulative; each reads and "
           "writes heads x head_dim^2 float32; from host-tracked positions, "
           "nothing fetched; exported by a file of that cache kind only"),
    Metric("sparse_queries_total", GAUGE,
           "queries of the block-sparse attention layers by the branch "
           "they took (dense: plain causal attention on the ring, before "
           "dense_len; sparse: scores, selection and a read of the selected "
           "blocks): one per position of a prompt's prefill and per decode "
           "step of a lane that holds a request, times the sparse layers; "
           "cumulative", labels=("branch",)),
    Metric("sparse_blocks_read_total", GAUGE,
           "ring blocks the sparse branch's read covered in decode steps "
           "(the first blocks, the window's, the picked ones), summed over "
           "steps, lanes that hold a request, sparse layers and KV heads; "
           "cumulative"),
    Metric("sparse_blocks_visible_total", GAUGE,
           "ring blocks a causal read would have covered, over the same "
           "steps, lanes, layers and heads; read over visible = the share "
           "of the ring the selection leaves to read"),
    Metric("sparse_kc_written_total", GAUGE,
           "compressed keys written (a mean over kernel_size keys, one "
           "every kernel_stride positions, by the step or slice that "
           "writes its last position), summed over the sparse layers; "
           "prefill and decode; cumulative"),
    # -- runtime-synthesized families --------------------------------------
    Metric("scheduler_", GAUGE,
           "continuous-scheduler family (ContinuousEngine.scheduler_stats). "
           "Point in time: lanes_live, pending, admission_inflight, "
           "adm_*, mem_pressure, batch_size. Cumulative since start, one "
           "add per wave (a wave = the admission slices queued ahead of "
           "one decode chunk, that chunk's dispatch, the previous chunk's "
           "fetch + harvest): waves, wave_seconds, lane_live_seconds + "
           "lane_idle_seconds (= batch_size x wave_seconds), "
           "fetch_wait_seconds, admit_seconds, admit_slices, admit_tokens, "
           "harvest_seconds, chunks_dispatched, admits_beside_live "
           "(admissions finished while other lanes decode) and "
           "admit_chunks_behind (summed over them: decode chunks the pass "
           "that finished the admission had dispatched without its lane; "
           "0 while the round runs ahead of the chunk), steps_run / "
           "steps_skipped (the fetched chunks' decode_chunk steps: those "
           "the chunk program ran, and those it left out because none of "
           "its lanes had anything left to decode), chunks_empty (chunks "
           "that ran no step: one behind every request that ends with no "
           "other lane alive) and end_disagreements (lanes whose end the "
           "device and the harvest found at different tokens; 0), "
           "lane_prefix_* / radix_prefix_*",
           prefix=True),
)


@dataclasses.dataclass(frozen=True)
class MemComponent:
    """One registered memory-ledger component (obs/memledger.py): a
    device-allocation surface that reports live byte counts into the
    ``hbm_bytes{component,model}`` family.  ``device=False`` marks a
    host-RAM tier (listed, but excluded from the HBM reconciliation
    sum).  ``always=True`` keeps the row at ZERO instead of dropping it
    — for gauges whose zero IS the alert condition (a fully exhausted
    free list must read 0, not "no data").  Mirrors :class:`Metric`:
    every ``MemLedger.register_component`` name must appear here —
    enforced at runtime (KeyError) and statically (lfkt-lint OBS003)."""

    name: str
    help: str = ""
    device: bool = True
    always: bool = False


#: THE memory-component catalog: every allocation surface the ledger may
#: attribute.  ``residual`` is computed (ground truth minus the sum of
#: device components), never registered.
MEM_COMPONENTS: dict[str, MemComponent] = {
    c.name: c for c in (
        MemComponent("weights",
                     "per-model resident weight bytes (Engine.weight_bytes"
                     " — the registry's HBM budget unit)"),
        MemComponent("kv_ring",
                     "serial dense KV ring (Engine._cache; allocated on "
                     "every engine, serving or not)"),
        MemComponent("kv_lanes",
                     "batched lane state: the lane engine's shared "
                     "decode pytree (parallel/batched.py)"),
        MemComponent("kv_scratch",
                     "the continuous scheduler's persistent prefill "
                     "scratch ring (engine/continuous.py)"),
        MemComponent("kv_arena_used",
                     "KV pool arena pages holding indexed cache content, "
                     "per radix namespace (model); model=(unindexed) is "
                     "allocated-but-uncommitted in-flight pages"),
        MemComponent("kv_arena_free",
                     "KV pool arena pages on the free list (allocated "
                     "HBM, no content); reported even at 0 — exhaustion "
                     "is the alert", always=True),
        MemComponent("host_spill",
                     "host-RAM KV spill tier (LFKT_KV_SPILL_PAGES)",
                     device=False),
        MemComponent("disagg_txbuf",
                     "disagg page-wire send queues: host bytes buffered "
                     "between page export and the socket (bounded by "
                     "LFKT_DISAGG_QUEUE_FRAMES x peers — "
                     "serving/disagg/transport.py)",
                     device=False),
        MemComponent("residual",
                     "ground truth minus every attributed device "
                     "component: bytes the ledger cannot explain "
                     "(computed, never registered)"),
    )
}


def lookup(name: str) -> Metric | None:
    """The catalog entry governing ``name``: exact match first, then the
    longest matching declared prefix family."""
    m = METRICS.get(name)
    if m is not None:
        return m
    best = None
    for entry in METRICS.values():
        if entry.prefix and name.startswith(entry.name):
            if best is None or len(entry.name) > len(best.name):
                best = entry
    return best


def markdown_table() -> str:
    """The docs/OBSERVABILITY.md metrics table — generated, never hand
    edited (tests/test_obs.py pins the docs block to this output)."""
    rows = ["| metric | type | labels | help |",
            "|---|---|---|---|"]
    for m in METRICS.values():
        name = f"{m.name}*" if m.prefix else m.name
        labels = ",".join(m.labels) if m.labels else ""
        rows.append(f"| `{name}` | {m.mtype} | {labels} | {m.help} |")
    return "\n".join(rows)


if __name__ == "__main__":
    print(markdown_table())
