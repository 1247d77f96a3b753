"""``python -m llama_fastapi_k8s_gpu_tpu.server`` — run the service.

Serves through the in-tree dependency-free ``httpd`` (the reference runs
gunicorn+UvicornWorker, reference docker/Dockerfile.app:12).  There is
exactly one worker process: the model is loaded once per process, so
``-w 1`` is load-bearing (SURVEY.md §1 L4).

The entry point also opens the start-up timeline (utils/startup.py): what
the process pays before a byte of the model is read, each stretch stamped
on ``time.time()`` and served with the rest at ``/health``
``engine.startup``.
"""

import time


def main():
    from ..utils.config import env_bool, knob

    # The reference scales with `gunicorn -w N` (reference
    # docker/Dockerfile.app:12).  On TPU that is the wrong axis: a chip
    # admits ONE claimant process, and N interchangeable workers would
    # load N copies of the model.  The principled axes are in-process
    # lanes (LFKT_BATCH_SIZE) within one chip, ROLE-SPECIALIZED
    # processes (LFKT_DISAGG_ROLE: a prefill tier streaming KV pages to
    # decode replicas — serving/disagg/) across chips on one host, and
    # k8s `replicas` across hosts — so any request for >1 worker is
    # refused loudly instead of silently serialized.
    workers = knob("LFKT_WORKERS")
    if workers != 1:
        raise SystemExit(
            f"LFKT_WORKERS={workers} refused: one worker per process is "
            "load-bearing (a TPU chip admits a single claimant; the model "
            "loads once per process). Scale within a chip with "
            "LFKT_BATCH_SIZE lanes; scale across processes by ROLE, not "
            "by copy — LFKT_DISAGG_ROLE=prefill|decode splits prefill "
            "and decode into cooperating processes streaming KV pages "
            "(docs/RUNBOOK.md 'Operating a split prefill/decode "
            "fleet'); scale across chips with k8s replicas.")
    host = knob("LFKT_HOST")
    port = knob("LFKT_PORT")
    # structured serving logs: one JSON object per line, every record
    # stamped with the active request id (obs/logctx.py) — the k8s log
    # pipeline's ingest format; the text format stays for in-tree dev runs
    if env_bool("LFKT_JSON_LOGS", default=True):
        import logging

        from ..obs.logctx import setup_json_logging

        root = logging.getLogger()
        for h in list(root.handlers):   # replace basicConfig's text handler
            root.removeHandler(h)
        setup_json_logging()
    # fleet router (serving/fleet/; docs/RUNBOOK.md "Running a replica
    # fleet"): the THIRD process role after serving and disagg tiers —
    # a prefix-affinity proxy over the replica fleet.  Checked BEFORE any
    # model machinery: a router pod has no engine and no jax — it is a
    # placement process.
    fleet_role = knob("LFKT_FLEET_ROLE", default="off")
    if fleet_role == "router":
        import logging

        from ..serving.fleet import run_router

        logging.basicConfig(level=logging.INFO)
        run_router(host, port)
        return
    if fleet_role != "off":
        from ..serving.fleet import FLEET_ROLES

        raise SystemExit(
            f"LFKT_FLEET_ROLE must be one of {'|'.join(FLEET_ROLES)}, "
            f"got {fleet_role!r}: replicas stay role=off; only the "
            "router process changes type (docs/RUNBOOK.md 'Running a "
            "replica fleet')")
    import jax

    from .. import T_IMPORTED
    from ..utils.jaxcache import setup_compile_cache
    from .app import app
    from .httpd import run

    # before_main: the interpreter and whatever a launcher imported before
    # this package (a launcher that touched JAX's devices paid the TPU
    # attach THERE, and backend_init below reads next to nothing);
    # imports: the package, the app module (server/__init__.py imports it
    # ahead of this file) and JAX
    tl = app.state.startup
    if tl.source == "proc_stat":
        tl.phase("before_main", tl.process_start_unix, T_IMPORTED)
    tl.phase("imports", T_IMPORTED, time.time())
    with tl.phase("backend_init") as ph:
        ph.attrs["platform"] = jax.default_backend()
    with tl.phase("compile_cache"):
        setup_compile_cache()
    run(app, host, port)


if __name__ == "__main__":
    main()
