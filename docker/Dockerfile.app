# App image: the serving framework on top of the TPU base.
# Mirrors the reference's two-stage split (docker/Dockerfile.app:1-12) with
# the registry base swapped for the TPU one.
FROM myregistry/lfkt-tpu-base:0.1.0

COPY docker/requirements.txt /app/requirements.txt
RUN pip install --no-cache-dir -r /app/requirements.txt

COPY llama_fastapi_k8s_gpu_tpu /app/llama_fastapi_k8s_gpu_tpu
RUN mkdir -p /app/models

# Persistent XLA compile cache: restarts of the same container (or a
# mounted volume — helm compileCache.*) skip jit warmup recompiles.
ENV JAX_COMPILATION_CACHE_DIR=/xla-cache
RUN mkdir -p /xla-cache

# Exactly one worker: the model is loaded once per process (reference
# Dockerfile.app:12 `gunicorn -w 1`); the module entrypoint enforces it.
CMD ["python", "-m", "llama_fastapi_k8s_gpu_tpu.server"]
