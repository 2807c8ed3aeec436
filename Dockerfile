# emqx_tpu broker image (deploy/docker analog of the reference).
# CPU JAX by default; on TPU hosts install the `tpu` extra (`pip install .[tpu]`).
FROM python:3.12-slim

WORKDIR /opt/emqx_tpu
COPY pyproject.toml README.md ./
COPY emqx_tpu ./emqx_tpu
RUN pip install --no-cache-dir .

# MQTT, WebSocket upgrade via the same TCP port set, mgmt API
EXPOSE 1883 8083 8883 18083

# config mounted at /opt/emqx_tpu/etc/emqx_tpu.json (EMQX_TPU__* env
# overrides also apply, bin/emqx HOCON_ENV_OVERRIDE_PREFIX analog)
VOLUME ["/opt/emqx_tpu/etc", "/opt/emqx_tpu/data"]

ENTRYPOINT ["emqx-tpu"]
CMD ["-c", "/opt/emqx_tpu/etc/emqx_tpu.json"]
