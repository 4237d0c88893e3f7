"""Synthetic image shards: the data-plane benchmark workload.

Writes deterministic pseudo-JPEG records — a label plus a
zlib-compressed uint8 image buffer — into multi-chunk RecordIO shards,
and provides the decode+augment function the feeder-saturation A/B
(scripts/data_plane_smoke.py) runs through
the worker pool. The decode cost profile matches what a real image
pipeline stresses:

- `zlib.decompress` and the numpy uint8->float normalize both RELEASE
  the GIL, so a thread pool gets true parallelism on them (like
  libjpeg-turbo in a real pipeline);
- an optional per-record `latency_s` models remote-storage fetch/decode
  latency (GCS reads are ~ms-scale) — the component a pod-scale feeder
  must overlap to reach 320k img/s; it sleeps off the GIL too.

Determinism: shard bytes depend only on (seed, shard index, sample
index), so the serial and pooled arms of the A/B read bit-identical
epochs from the same files.
"""
from __future__ import annotations

import os
import struct
import time
import zlib

import numpy as np

__all__ = ['write_shards', 'decode_record', 'make_decode_fn',
           'IMAGE_SHAPE']

IMAGE_SHAPE = (3, 32, 32)


def _record(rng, shape, label_classes):
    label = int(rng.randint(0, label_classes))
    raw = rng.randint(0, 256, size=int(np.prod(shape))).astype(np.uint8)
    # level 1: cheap-ish compress at write, real decompress work at read
    return struct.pack('<i', label) + zlib.compress(raw.tobytes(), 1)


def write_shards(dirpath, num_shards=4, samples_per_shard=256,
                 shape=IMAGE_SHAPE, label_classes=10, seed=0,
                 records_per_chunk=32):
    """Write `num_shards` RecordIO shard files under `dirpath` and return
    their (sorted) paths. Each shard carries multiple chunks
    (`records_per_chunk` approximate — the writer flushes by bytes), so
    chunk-granular dispatch has real work to stride across hosts."""
    os.makedirs(dirpath, exist_ok=True)
    from .. import recordio
    paths = []
    for si in range(int(num_shards)):
        rng = np.random.RandomState(int(seed) * 100003 + si)
        recs = [_record(rng, shape, label_classes)
                for _ in range(int(samples_per_shard))]
        chunk_bytes = max(1, int(records_per_chunk)) * max(
            len(recs[0]), 1)
        path = os.path.join(dirpath, 'synth-%05d.recordio' % si)
        recordio.write_recordio(path, recs, compressor=0,
                                max_chunk_bytes=chunk_bytes)
        paths.append(path)
    return paths


def decode_record(record, shape=IMAGE_SHAPE, latency_s=0.0):
    """record bytes -> (float32 image CHW in [-1, 1], int64 [1] label).
    The augment step (normalize) stands in for the usual crop/flip
    chain; both it and the decompress release the GIL."""
    if latency_s:
        time.sleep(latency_s)  # modeled remote-storage fetch latency
    (label,) = struct.unpack_from('<i', record)
    raw = zlib.decompress(record[4:])
    img = np.frombuffer(raw, np.uint8).astype(np.float32)
    img = (img / 127.5 - 1.0).reshape(shape)
    return img, np.array([label], np.int64)


def make_decode_fn(shape=IMAGE_SHAPE, latency_s=0.0):
    """A decode_fn closure for the worker pool (fork-safe: numpy/zlib
    only, no jax)."""
    def decode(record):
        return decode_record(record, shape=shape, latency_s=latency_s)
    return decode
