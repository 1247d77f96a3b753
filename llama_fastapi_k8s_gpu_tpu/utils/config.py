"""Env-overridable runtime settings — THE ``LFKT_*`` knob registry.

The reference hardcodes all of these as module constants (reference
api.py:13-19: model dir ``models``, ``MODEL_NAME``, ``MAX_CONTEXT_TOKENS=1024``,
``TIMEOUT_SECONDS=25``, ``MAX_QUEUE_SIZE=5``) and its Helm values never reach
the app as env vars (SURVEY.md §5 "Config / flag system").  Here the same
defaults are preserved, but every knob can be overridden through the
environment so the Helm chart can parameterize the app.

Every knob the package reads is declared ONCE, in :data:`KNOBS` below.
Package code reads knobs only through this module — :func:`get_settings`
for the Settings-backed ones, :func:`knob` / :func:`env_bool` for ad-hoc
reads — never ``os.environ`` directly.  That single-source-of-truth is
machine-enforced by lfkt-lint (rules CFG001-005, docs/LINT.md): a raw
``os.environ`` read of an LFKT_ name, an unregistered accessor call, an
undocumented registered knob, and a helm-chart reference to a name this
registry doesn't know are all tier-1 test failures.  The full catalog with
defaults and help text: docs/CONFIG.md.
"""

from __future__ import annotations

import dataclasses
import os


def _env(name: str, default, cast=str):  # lfkt: noqa[JIT001] -- trace-time read: kernel-variant knobs are read while jit traces and the value is keyed into every jit/lru cache (ops/pallas/qmatmul._env_variant)
    raw = os.environ.get(name)
    if raw is None:
        return default
    if cast is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    return cast(raw)


@dataclasses.dataclass(frozen=True)
class Settings:
    # Identical defaults to reference api.py:13-19.
    model_dir: str = "models"
    model_name: str = "Lexi-Llama-3-8B-Uncensored_Q4_K_M.gguf"
    # -- multi-model serving (docs/MULTIMODEL.md; ROADMAP item 5) ----------
    # declarative model manifest: name=path[:knob=value;...] entries,
    # comma-separated (serving/manifest.py).  Empty (the default) keeps the
    # single-model LFKT_MODEL_DIR/LFKT_MODEL_NAME path byte-for-byte.
    models: str = ""
    # the alias served when a request names no model= (default: the
    # manifest's first entry)
    default_model: str = ""
    # HBM budget for the fleet's WEIGHTS, in MB (0 = unlimited): the
    # registry refuses at load time, with per-model attribution, when the
    # manifest cannot fit — instead of OOMing at first traffic
    hbm_weight_budget_mb: float = 0.0
    max_context_tokens: int = 1024
    timeout_seconds: float = 25.0
    max_queue_size: int = 5
    # total wall-clock bound for one /response/stream response; the
    # per-chunk-gap timeout alone would let a slow-dripping generation hold
    # its queue slot indefinitely (no reference equivalent: it has no
    # streaming at all, reference api.py:58)
    stream_deadline_seconds: float = 300.0
    # graceful-shutdown budget: on SIGTERM in-flight requests get this long
    # to finish (gunicorn graceful_timeout analogue — the reference's
    # termination behavior at docker/Dockerfile.app:12).  Honored by both
    # the in-tree httpd and the uvicorn path; keep the pod's
    # terminationGracePeriodSeconds above it (helm derives grace from the
    # same values knob)
    drain_seconds: float = 30.0
    # slowloris guard (in-tree httpd): once a request line has arrived the
    # headers+body must finish arriving within this window, else 408 +
    # Connection: close instead of holding the socket forever
    read_timeout: float = 30.0

    # -- resilience layer (docs/RUNBOOK.md "Degraded-mode operations") -----
    # engine watchdog: detects stalled decode/hung device calls (no beat
    # for stall_seconds while work is in flight), exception bursts, and a
    # dead scheduler loop; trips to DEGRADED (readiness 503, liveness 200),
    # fails in-flight futures with 503, and runs bounded in-process
    # recovery with exponential backoff — escalating to DEAD (liveness
    # 503 → pod restart) after watchdog_max_recoveries trips.
    watchdog: bool = True
    watchdog_stall_seconds: float = 60.0
    watchdog_poll_seconds: float = 1.0
    watchdog_max_recoveries: int = 3
    watchdog_error_burst: int = 5
    watchdog_error_window: float = 30.0
    watchdog_backoff_seconds: float = 1.0
    watchdog_backoff_max: float = 60.0

    # Fixed sampling parameters the reference passes at api.py:59-62; the
    # remaining knobs take llama-cpp-python 0.2.77 defaults (top_k=40,
    # min_p=0.05, repeat_penalty=1.1) because the reference omits them.
    temperature: float = 1.2
    top_p: float = 0.9
    frequency_penalty: float = 0.7
    presence_penalty: float = 0.8
    top_k: int = 40
    min_p: float = 0.05
    repeat_penalty: float = 1.1

    # TPU-native knobs (no reference equivalent).
    max_gen_tokens: int = 512
    decode_chunk: int = 8           # device-side tokens per host round-trip.
    # Measured trade-off (docs/bench 2026-07-30): single-stream decode
    # rises mildly with chunk size (+~1% at 1k ctx, +4.7% at 8k for 32 vs
    # 8 — bench.py reports its sweep's best either way), but the chunk is
    # ALSO the continuous scheduler's admission/stream cadence: at 16 the
    # 8-lane aggregate dropped 160 -> 108 tok/s and stream TTFT doubled
    # (209 -> 407 ms).  8 is the serving default; single-stream batch
    # callers can raise LFKT_DECODE_CHUNK.
    prefill_buckets: str = "128,256,512,1024"  # padded prompt shapes to bound recompiles
    weight_format: str = "auto"     # auto | bf16 | int8 | q4k
    attn_impl: str = "auto"         # auto | xla | pallas (prefill flash kernel)
    kv_dtype: str = "bf16"          # bf16 | int8 — int8 halves KV-cache HBM
    #                                 (values int8 + per-head per-token f32
    #                                 scales) and streams int8 through the
    #                                 attention reads; docs/KV_CACHE.md
    # serial-engine prompt-prefix KV reuse (llama.cpp's prompt-cache
    # analogue): when consecutive prompts share a token prefix — the
    # reference workload re-sends persona + full history every turn —
    # prefill only the suffix.  The lane engine has its own (below).
    prefix_cache: bool = True
    # the continuous scheduler's analogue: admissions whose prompt shares
    # a freed lane's conversation history snapshot that lane's KV and
    # prefill only the suffix slices (chunk-aligned).  ON by default since
    # the admission controller closed the admission/decode interference
    # gap (round 6); explicit-seed requests still bypass it (the
    # reproducibility contract).
    lane_prefix_cache: bool = True
    # block-paged KV pool + shared radix-tree prefix cache
    # (parallel/kvpool.py; docs/RUNBOOK.md "Sizing the KV page pool"):
    # KV pages live in one preallocated arena fronted by a radix tree
    # keyed on token prefixes, so shared system prompts prefill once per
    # process and multi-turn requests resume from their last committed
    # page regardless of lane.  OFF by default — the dense per-lane ring
    # stays the A/B control (greedy decode is bit-identical either way,
    # pinned by tests/test_kv_paged_engines.py).
    kv_paged: bool = False
    kv_page_tokens: int = 128       # token slots per pool page
    kv_pool_pages: int = 0          # arena size in pages (0 = auto:
    #                                 4 full contexts' worth)
    kv_spill_pages: int = 0         # host-RAM spill tier capacity in
    #                                 pages (0 = evictions discard)
    prefill_chunk: int = 256        # prefill slice size: the continuous
    #                                 scheduler's admission slices AND the
    #                                 serial engine's overlapped bucket
    #                                 slices (docs/RUNBOOK.md "Tuning
    #                                 long-context TTFT")
    # serial-engine overlapped chunked prefill: how many un-synced prefill
    # slices may queue on the device at once (slice i+1's host prep +
    # dispatch overlap slice i's compute).  0 restores monolithic
    # bucket-sized prefill; slicing only engages when the prompt bucket
    # exceeds prefill_chunk, so short prompts are untouched either way.
    prefill_overlap: int = 2
    adm_budget: int = 512           # admission prefill tokens per scheduler
    #                                 wave: the static value when the
    #                                 admission controller is off, and the
    #                                 controller's initial/base budget when
    #                                 it is on
    # admission controller (engine/continuous.py AdmissionController):
    # derives each wave's prefill-token budget from an EMA of measured
    # lane-idle fraction and decode slack (harvest-fetch wait) instead of
    # the static adm_budget — budget rises while lanes sit idle, shrinks
    # under decode pressure, and never drops below one slice per wave (a
    # deadline-bearing admission always makes progress).
    adm_controller: bool = True
    adm_ema_alpha: float = 0.25     # EMA weight of the controller's signals
    # >1 switches the server from the serial Engine to ContinuousEngine
    # with that many lanes: slot-based continuous batching on the one
    # device; free lanes admit new requests at every chunk boundary
    batch_size: int = 1
    # -- disaggregated prefill/decode (serving/disagg/; docs/RUNBOOK.md
    # "Operating a split prefill/decode fleet") ----------------------------
    # role of this process in a split fleet: "off" (default — the single-
    # process serving path, byte-for-byte unchanged), "prefill" (runs the
    # KV-page service: prefills prompts and streams finished pages),
    # "decode" (forwards admitted prompts to the prefill peer, restores
    # the returned pages into its paged arena, decodes), or "both" (the
    # in-process loopback: page service + client on one engine — the
    # tier-1-testable / bench-A/B arm).  prefill/decode/both require
    # LFKT_KV_PAGED=1: pages ARE the wire format.
    disagg_role: str = "off"
    # decode role: the prefill tier's page service, "host:port"
    disagg_peer: str = ""
    # prefill role: page-service bind address and port (0 = ephemeral,
    # loopback/tests)
    disagg_bind: str = "0.0.0.0"
    disagg_port: int = 8470
    # per-hop wire budget: a remote prefill that cannot complete within
    # min(this, the request's remaining deadline) aborts on both sides and
    # the decode replica falls back to LOCAL prefill with attribution
    disagg_timeout_seconds: float = 5.0
    # bounded page-frame send queue per peer connection (backpressure: a
    # slow wire blocks the prefill tier's page export, never grows memory;
    # the buffered bytes are the memory ledger's disagg_txbuf component)
    disagg_queue_frames: int = 32
    # -- fleet tier (serving/fleet/; docs/RUNBOOK.md "Running a replica
    # fleet") --------------------------------------------------------------
    # "router" turns this process into the prefix-affinity proxy in front
    # of the replica fleet (no engine, no jax): requests key on their
    # conversation/system-prompt prefix and rendezvous-hash to the
    # replica whose radix cache is warm for them.  "off" (default) = a
    # plain serving replica.
    fleet_role: str = "off"
    # router: static replica list, host:port comma-separated (tests,
    # docker-compose).  In k8s prefer fleet_dns.
    fleet_peers: str = ""
    # router: headless-Service DNS name:port, re-resolved every probe
    # cycle — one A record per ready pod, so scale-out/in needs no
    # router restart
    fleet_dns: str = ""
    # router placement policy: "affinity" (rendezvous on the prefix key)
    # or "roundrobin" (the A/B control arm — bench_server.py fleet arm)
    fleet_policy: str = "affinity"
    # router: peer /health/ready probe period
    fleet_probe_seconds: float = 2.0
    # router: first ejection backoff (doubles per consecutive failure)
    fleet_eject_backoff_seconds: float = 1.0
    fleet_eject_backoff_max: float = 30.0
    # router: backend connect + response-head deadline; body progress
    # rides stream_deadline_seconds
    fleet_proxy_timeout_seconds: float = 5.0
    # router: per-request spill-replay budget — replicas tried beyond the
    # rendezvous owner before the router answers 503 + Retry-After
    # (fleet_spills_total{reason="budget"}) instead of walking the whole
    # rendezvous order on a poisoned request
    fleet_max_spills: int = 3
    # -- fleet KV migration (serving/fleet/migrate.py; docs/RUNBOOK.md
    # "Surviving pod churn") -----------------------------------------------
    # arm warm-page migration on this replica: the page service +
    # pull-on-remap client + graceful drain-push + scale-out warm-up
    # (requires LFKT_KV_PAGED=1; off = all paths byte-for-byte unchanged)
    migrate: bool = False
    # migration page-service bind address
    migrate_bind: str = "0.0.0.0"
    # migration page-service port (0 = ephemeral; peers discover the
    # bound port through the /health "migration" block, never by config)
    migrate_port: int = 8471
    # this replica's own fleet address (the host:port peers reach its
    # HTTP port on) — excluded from drain-successor ranking; in k8s the
    # downward-API pod IP (helm/templates/deployment.yaml)
    migrate_self: str = ""
    # one migration wire hop's budget; pulls are additionally clipped to
    # the request's remaining deadline (a dead peer costs milliseconds,
    # never a hang)
    migrate_timeout_seconds: float = 2.0
    # hottest radix prefixes moved per peer (scale-out warm-up pulls
    # them, graceful drain pushes them)
    migrate_top_k: int = 8
    # graceful drain: total budget for pushing hot prefixes to the
    # rendezvous successors before termination proceeds (added to the
    # pod's terminationGracePeriodSeconds by the chart)
    migrate_drain_seconds: float = 5.0
    # router: a peer added or readmitted within this window is "fresh"
    # (cold cache) — requests it owns carry a prior-owner hint so the
    # pod can pull warm pages before prefilling (0 disables the hint)
    migrate_fresh_seconds: float = 600.0
    # live manifest reload (POST /admin/models/reload, SIGHUP): bounded
    # wait for a removed model's in-flight requests and its radix
    # namespace's pinned pages before the weights release
    reload_drain_seconds: float = 30.0

    @property
    def model_path(self) -> str:
        return os.path.join(self.model_dir, self.model_name)

    @property
    def prefill_bucket_list(self) -> list[int]:
        return sorted(int(x) for x in self.prefill_buckets.split(",") if x.strip())


@dataclasses.dataclass(frozen=True)
class Knob:
    """One registered env knob.  ``serving=True`` marks knobs a deployment
    must be able to set per-pod — lfkt-lint (CFG003) checks they are
    plumbed or documented in the Helm chart; every knob must additionally
    appear in docs (CFG002, see docs/CONFIG.md)."""

    name: str
    cast: type = str
    help: str = ""
    serving: bool = False
    default: object = None          # ad-hoc knobs only; Settings-backed
    #                                 knobs default from the Settings field
    field: str | None = None        # Settings field (wired in _register)


_SETTINGS_FIELDS = {f.name for f in dataclasses.fields(Settings)}


def _register(*knobs: Knob) -> dict[str, Knob]:
    out: dict[str, Knob] = {}
    for k in knobs:
        field = k.name[len("LFKT_"):].lower()
        if field in _SETTINGS_FIELDS:
            k = dataclasses.replace(k, field=field)
        out[k.name] = k
    return out


#: THE registry: every LFKT_* env var any package code reads.  Settings-
#: backed knobs (the majority) take their default and docstring context
#: from the Settings field of the same lowercased name; ad-hoc knobs carry
#: an explicit ``default``.  docs/CONFIG.md mirrors this table.
KNOBS: dict[str, Knob] = _register(
    # -- Settings-backed (reference-parity serving surface) ----------------
    Knob("LFKT_MODEL_DIR", str, "GGUF directory", serving=True),
    Knob("LFKT_MODEL_NAME", str, "GGUF file name", serving=True),
    # -- multi-model serving (docs/MULTIMODEL.md) --------------------------
    Knob("LFKT_MODELS", str,
         "multi-model manifest: name=path[:knob=value;...],... "
         "(empty = single-model LFKT_MODEL_NAME)", serving=True),
    Knob("LFKT_DEFAULT_MODEL", str,
         "alias served when a request names no model= "
         "(default: first manifest entry)", serving=True),
    Knob("LFKT_HBM_WEIGHT_BUDGET_MB", float,
         "HBM budget for the fleet's weights, MB (0 = unlimited); "
         "exceeded = load-time refusal with attribution", serving=True),
    Knob("LFKT_MAX_CONTEXT_TOKENS", int, "context window", serving=True),
    Knob("LFKT_TIMEOUT_SECONDS", float, "admission future timeout (408)",
         serving=True),
    Knob("LFKT_MAX_QUEUE_SIZE", int, "admission queue bound (503)",
         serving=True),
    Knob("LFKT_STREAM_DEADLINE_SECONDS", float,
         "total wall budget of one SSE stream"),
    Knob("LFKT_DRAIN_SECONDS", float, "graceful-shutdown budget",
         serving=True),
    Knob("LFKT_READ_TIMEOUT", float, "httpd slowloris guard (408)"),
    # -- watchdog / resilience --------------------------------------------
    Knob("LFKT_WATCHDOG", bool, "enable the engine watchdog"),
    Knob("LFKT_WATCHDOG_STALL_SECONDS", float, "stalled-decode trip bound"),
    Knob("LFKT_WATCHDOG_POLL_SECONDS", float, "watchdog sampling period"),
    Knob("LFKT_WATCHDOG_MAX_RECOVERIES", int, "trips before DEAD"),
    Knob("LFKT_WATCHDOG_ERROR_BURST", int, "errors per window that trip"),
    Knob("LFKT_WATCHDOG_ERROR_WINDOW", float, "burst window seconds"),
    Knob("LFKT_WATCHDOG_BACKOFF_SECONDS", float, "first recovery backoff"),
    Knob("LFKT_WATCHDOG_BACKOFF_MAX", float, "recovery backoff ceiling"),
    # -- sampling (reference api.py:59-62 + llama-cpp-python defaults) -----
    Knob("LFKT_TEMPERATURE", float, "sampling temperature"),
    Knob("LFKT_TOP_P", float, "nucleus sampling mass"),
    Knob("LFKT_FREQUENCY_PENALTY", float, "frequency penalty"),
    Knob("LFKT_PRESENCE_PENALTY", float, "presence penalty"),
    Knob("LFKT_TOP_K", int, "top-k cutoff"),
    Knob("LFKT_MIN_P", float, "min-p cutoff"),
    Knob("LFKT_REPEAT_PENALTY", float, "repetition penalty"),
    # -- TPU-native engine knobs -------------------------------------------
    Knob("LFKT_MAX_GEN_TOKENS", int, "default completion budget"),
    Knob("LFKT_DECODE_CHUNK", int, "device tokens per host round-trip"),
    Knob("LFKT_PREFILL_BUCKETS", str, "padded prompt shapes (csv)"),
    Knob("LFKT_WEIGHT_FORMAT", str, "auto|bf16|int8|q4k"),
    Knob("LFKT_ATTN_IMPL", str, "auto|xla|pallas"),
    Knob("LFKT_KV_DTYPE", str, "bf16|int8 KV cache (docs/KV_CACHE.md)"),
    Knob("LFKT_PREFIX_CACHE", bool, "serial-engine prompt-prefix KV reuse"),
    Knob("LFKT_LANE_PREFIX_CACHE", bool, "lane-claim admission KV reuse"),
    Knob("LFKT_KV_PAGED", bool,
         "block-paged KV pool + radix-tree prefix cache (0 = dense ring)"),
    Knob("LFKT_KV_PAGE_TOKENS", int, "token slots per KV pool page"),
    Knob("LFKT_KV_POOL_PAGES", int, "KV pool arena size in pages (0 = auto)"),
    Knob("LFKT_KV_SPILL_PAGES", int,
         "host-RAM KV spill tier capacity in pages (0 = off)"),
    Knob("LFKT_PREFILL_CHUNK", int, "prefill slice tokens beside live lanes "
         "and of a prompt's tail (admission + serial overlapped prefill; "
         "where nobody decodes behind it a prompt is cut 1024 wide first: "
         "engine/slices.py)"),
    Knob("LFKT_PREFILL_OVERLAP", int,
         "overlapped-prefill depth (0 = monolithic bucket prefill)"),
    Knob("LFKT_ADM_BUDGET", int,
         "admission tokens per wave (controller base / static value)"),
    Knob("LFKT_ADM_CONTROLLER", bool,
         "EMA admission controller for the per-wave prefill budget"),
    Knob("LFKT_ADM_EMA_ALPHA", float,
         "admission-controller EMA weight"),
    Knob("LFKT_BATCH_SIZE", int, "serving lanes (>1: continuous batching)"),
    # -- disaggregated prefill/decode (serving/disagg/) --------------------
    Knob("LFKT_DISAGG_ROLE", str,
         "off|prefill|decode|both — split prefill/decode fleet role "
         "(serving/disagg/; requires LFKT_KV_PAGED=1 when not off)",
         serving=True),
    Knob("LFKT_DISAGG_PEER", str,
         "decode role: prefill tier page service, host:port", serving=True),
    Knob("LFKT_DISAGG_BIND", str, "prefill role: page-service bind address"),
    Knob("LFKT_DISAGG_PORT", int,
         "prefill role: page-service port (0 = ephemeral)", serving=True),
    Knob("LFKT_DISAGG_TIMEOUT_SECONDS", float,
         "per-hop wire budget before the decode side falls back to "
         "local prefill"),
    Knob("LFKT_DISAGG_QUEUE_FRAMES", int,
         "bounded page-frame send queue per peer (backpressure)"),
    # -- fleet tier (serving/fleet/) ---------------------------------------
    Knob("LFKT_FLEET_ROLE", str,
         "off|router — router runs the prefix-affinity proxy over the "
         "replica fleet instead of a serving engine (serving/fleet/)",
         serving=True),
    Knob("LFKT_FLEET_PEERS", str,
         "router: static replica list host:port[,host:port...]",
         serving=True),
    Knob("LFKT_FLEET_DNS", str,
         "router: headless-Service name:port resolved per probe cycle "
         "(one A record per ready replica)", serving=True),
    Knob("LFKT_FLEET_POLICY", str,
         "router placement: affinity (rendezvous on the prefix key) | "
         "roundrobin (A/B control)", serving=True),
    Knob("LFKT_FLEET_PROBE_SECONDS", float,
         "router: peer /health/ready probe period", serving=True),
    Knob("LFKT_FLEET_EJECT_BACKOFF_SECONDS", float,
         "router: first ejection backoff (doubles per failure)"),
    Knob("LFKT_FLEET_EJECT_BACKOFF_MAX", float,
         "router: ejection backoff ceiling"),
    Knob("LFKT_FLEET_PROXY_TIMEOUT_SECONDS", float,
         "router: backend connect + response-head deadline",
         serving=True),
    Knob("LFKT_FLEET_MAX_SPILLS", int,
         "router: spill replays per request before 503 + Retry-After "
         "(fleet_spills_total{reason=budget})", serving=True),
    # -- fleet KV migration (serving/fleet/migrate.py) ---------------------
    Knob("LFKT_MIGRATE", bool,
         "warm KV-page migration: pull-on-remap + graceful drain-push + "
         "scale-out warm-up (requires LFKT_KV_PAGED=1)", serving=True),
    Knob("LFKT_MIGRATE_BIND", str, "migration page-service bind address"),
    Knob("LFKT_MIGRATE_PORT", int,
         "migration page-service port (0 = ephemeral; discovered via "
         "/health)", serving=True),
    Knob("LFKT_MIGRATE_SELF", str,
         "this replica's own fleet address host:port (drain-successor "
         "self-exclusion)", serving=True),
    Knob("LFKT_MIGRATE_TIMEOUT_SECONDS", float,
         "one migration wire hop's budget; pulls also clip to the "
         "request's remaining deadline", serving=True),
    Knob("LFKT_MIGRATE_TOP_K", int,
         "hottest prefixes moved per peer (warm-up pulls, drain pushes)",
         serving=True),
    Knob("LFKT_MIGRATE_DRAIN_SECONDS", float,
         "graceful drain: total hot-page push budget before termination "
         "proceeds", serving=True),
    Knob("LFKT_MIGRATE_FRESH_SECONDS", float,
         "router: peers (re)admitted within this window carry a "
         "prior-owner hint for pull-on-remap (0 disables)", serving=True),
    Knob("LFKT_RELOAD_DRAIN_SECONDS", float,
         "live model removal: bounded wait for in-flight requests + "
         "pinned namespace pages before weights release", serving=True),
    # -- ad-hoc knobs (read via knob()/env_bool(), not Settings) -----------
    Knob("LFKT_HOST", str, "bind address (server/__main__.py)",
         default="0.0.0.0"),
    Knob("LFKT_PORT", int, "bind port (server/__main__.py)", default=8000),
    Knob("LFKT_WORKERS", int, "must stay 1: one model per process",
         default=1),
    Knob("LFKT_PROFILE_DIR", str,
         "arms GET /debug/profile (bounded XProf capture into this "
         "directory, utils/tracing.py) and the lfkt.* phase annotations "
         "inside it (obs/trace.py phase); nothing else is profiled",
         serving=True, default=""),
    # -- lfkt-perf (obs/devtime.py + obs/slo.py; docs/SLO.md) --------------
    Knob("LFKT_DEVTIME", bool,
         "per-program compile/dispatch attribution (obs/devtime.py; "
         "0 disarms the registry)", serving=True, default=True),
    Knob("LFKT_RECOMPILE_BUDGET", int,
         "distinct jit signatures per program before a recompile storm "
         "is flagged", serving=True, default=32),
    Knob("LFKT_SLO_TTFT_P95_S", float,
         "SLO: TTFT bound (seconds) 95% of requests must beat, per "
         "prefill bucket", serving=True, default=1.0),
    Knob("LFKT_SLO_DECODE_FLOOR_TPS", float,
         "SLO: decode tok/s floor 95% of requests must clear",
         serving=True, default=10.0),
    Knob("LFKT_SLO_ERROR_RATE", float,
         "SLO: 5xx error-rate budget over each burn window",
         serving=True, default=0.01),
    Knob("LFKT_SLO_QUEUE_P95_S", float,
         "SLO: admission-queue wait bound (seconds) at the 95th percentile",
         serving=True, default=0.5),
    Knob("LFKT_SLO_WINDOWS", str,
         "SLO burn-rate windows, csv seconds (short,long)",
         serving=True, default="300,3600"),
    # -- lfkt-mem (obs/memledger.py + obs/flightrec.py; docs/RUNBOOK.md
    # "Diagnosing HBM OOM") -------------------------------------------------
    Knob("LFKT_MEM_LEDGER", bool,
         "live HBM memory ledger: component attribution + /debug/memory "
         "+ hbm_bytes gauges (0 disarms; obs/memledger.py)",
         serving=True, default=True),
    Knob("LFKT_MEM_PRESSURE_FRACTION", float,
         "device HBM headroom fraction below which the admission "
         "controller treats memory as pressure and cuts its budget",
         serving=True, default=0.05),
    Knob("LFKT_INCIDENT_DIR", str,
         "incident flight-recorder directory (empty = recorder off; "
         "mount a pod volume so bundles survive restarts)",
         serving=True, default=""),
    Knob("LFKT_INCIDENT_RING", int,
         "max incident bundles kept on disk (oldest pruned)",
         serving=True, default=16),
    Knob("LFKT_INCIDENT_DEBOUNCE_S", float,
         "per-kind minimum seconds between incident bundles (a burst "
         "records once, not once per error)", default=30.0),
    Knob("LFKT_INCIDENT_LOG_LINES", int,
         "structured log lines retained for a bundle's log_tail",
         default=100),
    # -- lfkt-obs (obs/trace.py; docs/OBSERVABILITY.md) --------------------
    Knob("LFKT_TRACE_SAMPLE", float,
         "fraction of requests traced (0 disarms the tracer)",
         serving=True, default=1.0),
    Knob("LFKT_TRACE_RING", int,
         "completed traces kept for /debug/traces", serving=True,
         default=256),
    Knob("LFKT_JSON_LOGS", bool,
         "JSON access/serving logs with request ids (server/__main__.py)",
         default=True),
    Knob("LFKT_NATIVE", bool, "C++ GGUF load path (0 forces numpy)",
         default=True),
    Knob("LFKT_LOAD_OVERLAP", bool,
         "overlap per-layer host→device transfer with dequant",
         default=True),
    Knob("LFKT_FAULTS", str,
         "fault-injection arming spec (utils/faults.py; drills only)",
         default=""),
    Knob("LFKT_FLASH_KV_UNROLL", int,
         "flash-attention fused KV sub-blocks per grid step "
         "(ops/pallas/attention.py)", default=4),
    Knob("LFKT_Q4K_KERNEL", str,
         "fused Q4_K variant of the dense calls (A/B)", default=""),
    Knob("LFKT_Q5K_KERNEL", str, "fused Q5_K kernel variant (A/B)",
         default=""),
    Knob("LFKT_Q6K_KERNEL", str,
         "fused Q6_K LAYOUT a load writes: split (default) | pre",
         default=""),
)


def knob(name: str, default=None, cast=None):
    """Registered ad-hoc env read — the ONLY way package code outside this
    module reads an ``LFKT_*`` var (lfkt-lint CFG001/CFG005).  ``default``
    overrides the registry default at call sites whose natural default is
    contextual (e.g. kernel-variant tables)."""
    k = KNOBS.get(name)
    if k is None:
        raise KeyError(
            f"{name} is not in the LFKT knob registry (utils/config.py); "
            "register it before reading it")
    if default is None:
        # Settings-backed knobs keep their documented default even through
        # this accessor (their Knob.default is None by construction);
        # ad-hoc knobs carry theirs on the Knob entry
        default = k.default if k.field is None else getattr(Settings, k.field)
    return _env(name, default, cast or k.cast)


def env_bool(name: str, default: bool = False) -> bool:
    """THE truthy-env convention (one parser: '1'/'true'/'yes'/'on').
    Direct-engine-construction paths (bench_server.py, models/params.py)
    must use this instead of re-implementing the tuple and silently
    diverging on accepted spellings.  LFKT_* names must be registered."""
    if name.startswith("LFKT_") and name not in KNOBS:
        raise KeyError(
            f"{name} is not in the LFKT knob registry (utils/config.py); "
            "register it before reading it")
    return _env(name, default, bool)


def get_settings() -> Settings:
    """Build Settings from the registry: every Settings-backed knob reads
    its env var with the Settings field's default — the registry and the
    dataclass cannot drift (tests/test_lint.py pins the mapping)."""
    kw = {}
    for name, k in KNOBS.items():
        if k.field is not None:
            kw[k.field] = _env(name, getattr(Settings, k.field), k.cast)
    return Settings(**kw)
