# Deployment container for the TPU-native Beacon (the reference's
# docker/Dockerfile + init.sh toolchain role, SURVEY.md L8 — except this
# build needs no AWS SDK, lambda runtime, or htslib/bcftools: the
# framework carries its own BGZF/VCF machinery and the only native
# dependency is zlib, compiled on first use via g++).
#
#   docker build -t sbeacon-tpu .
#   docker run -p 5000:5000 -v /data:/data sbeacon-tpu \
#       --data-root /data [--worker http://worker1:5100 ...]
#
# Worker hosts run the same image with a different entrypoint. Workers
# serve all genomic data, so keep them on a private network AND set a
# shared BEACON_WORKER_TOKEN (required on /search and /datasets; the
# coordinator sends it automatically). --host must be widened
# explicitly — the worker CLI binds loopback by default:
#   docker run -p 5100:5100 -v /data:/data -e BEACON_WORKER_TOKEN=... \
#       --entrypoint python sbeacon-tpu -m sbeacon_tpu.parallel.dispatch \
#       --data-root /data --port 5100 --host 0.0.0.0
#
# THIS IMAGE IS CPU-ONLY: it installs jax[cpu], pinned to the version
# the code is written and checked against (JAX 0.9.0). It serves and
# tests on the CPU backend; it cannot see a TPU. For a TPU VM, build
# from a base that carries jax[tpu]==0.9.0 with its matching libtpu
# (0.0.34) and run ONE server or worker process per host.
# Compiled programs persist in /app/.jax_cache, which dies with the
# container: set JAX_COMPILATION_CACHE_DIR to a directory on a mounted
# volume (docker-compose.yml uses /data/jax-cache) to keep them across
# restarts. Every program is kept there, whatever it took to compile.

FROM python:3.12-slim

RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ zlib1g-dev \
    && rm -rf /var/lib/apt/lists/*

RUN pip install --no-cache-dir \
    "jax[cpu]==0.9.0" numpy jsonschema cryptography

WORKDIR /app
COPY sbeacon_tpu ./sbeacon_tpu

# pre-build the native library so first-request latency stays flat;
# force=True so a host-built .so that slipped past .dockerignore can
# never shadow a compile for THIS image's toolchain
RUN python -c "from sbeacon_tpu import native; native.build(force=True)"

EXPOSE 5000
ENTRYPOINT ["python", "-m", "sbeacon_tpu.api.server"]
CMD ["--port", "5000"]
