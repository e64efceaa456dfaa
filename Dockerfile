# Container image for a petals_tpu swarm server or DHT bootstrap on a TPU VM
# (the reference ships a CUDA image, /root/reference/Dockerfile — this is its
# TPU-native counterpart: libtpu comes from the jax[tpu] wheel, no conda).
#
#   docker build -t petals_tpu .
#   docker run --privileged --network host \
#       -v /cache:/cache -e PETALS_TPU_CACHE=/cache \
#       -e JAX_COMPILATION_CACHE_DIR=/cache/jax \
#       petals_tpu python -m petals_tpu.cli.run_server MODEL --initial_peers ...
#
# One server process per chip: on a multi-chip VM start one container (or
# process) per chip with TPU_VISIBLE_CHIPS=<n> TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1
# TPU_PROCESS_BOUNDS=1,1,1 (README "Running on the chip").
#
# --privileged + host networking are the standard TPU-VM container settings
# (the TPU driver is exposed via /dev and the swarm needs inbound dials).

FROM python:3.12-slim

LABEL repository="petals_tpu"

WORKDIR /home

RUN apt-get update && apt-get install -y --no-install-recommends \
  build-essential \
  g++ \
  && apt-get clean autoclean && rm -rf /var/lib/apt/lists/* /tmp/* /var/tmp/*

# TPU-enabled jax (pulls libtpu), the one series pyproject.toml declares and
# the code is written against; CPU torch only for checkpoint IO
RUN pip install --no-cache-dir "jax[tpu]>=0.9,<0.10" -f https://storage.googleapis.com/jax-releases/libtpu_releases.html && \
    pip install --no-cache-dir torch --index-url https://download.pytorch.org/whl/cpu && \
    rm -rf ~/.cache/pip

VOLUME /cache
ENV PETALS_TPU_CACHE=/cache

COPY . petals_tpu/
RUN pip install --no-cache-dir -e petals_tpu && rm -rf ~/.cache/pip

WORKDIR /home/petals_tpu/
CMD ["python", "-m", "petals_tpu.cli.run_dht", "--host", "0.0.0.0"]
