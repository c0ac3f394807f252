"""minio_tpu: a TPU-native, S3-compatible erasure-coded object storage
data-plane with the capabilities of the reference MinIO (kubegems/minio).

Hot paths (Reed-Solomon GF(2^8) coding, HighwayHash bitrot, heal
reconstruction) run as jitted JAX programs; the surrounding runtime
(storage, quorum, object layer, S3 API) is host-side Python/C++.
"""

__version__ = "0.1.0"
