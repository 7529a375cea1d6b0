"""Dataloaders: resumable host-side batcher + global-array feeders.

Parity: reference `dolomite_engine/data/dataloader.py:12-104`:
  - `ResumableDataLoader` (dataset+sampler state_dict) -> same here, minus torch.
  - `DispatchingDataLoader` (node-rank0 loads batch x node_size, NCCL-broadcasts tensors,
    ranks slice their shard) -> two TPU answers:
      * `ShardedDataLoader` (the default): each HOST loads only its shard and
        `jax.make_array_from_process_local_data` assembles the global sharded array — zero
        broadcast traffic; strictly better WHEN every host mounts the corpus.
      * `DispatchingDataLoader` (`distributed_args.dispatching_dataloader: true`): only
        process 0 touches storage; per-step batches ride a device-collective broadcast
        (`multihost_utils.broadcast_one_to_all`, the XLA equivalent of the reference's
        NCCL broadcast) — for single-host-storage setups, the reference's exact tradeoff.
"""

from __future__ import annotations

import json
from typing import Callable, Iterator

import jax
import numpy as np

from ..utils.telemetry import get_telemetry


class ResumableDataLoader:
    def __init__(
        self,
        dataset,
        batch_size: int,
        sampler,
        collate_fn: Callable | None = None,
        drop_last: bool = False,
    ) -> None:
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = sampler
        self.collate_fn = collate_fn
        self.drop_last = drop_last

    def __iter__(self) -> Iterator:
        batch = []
        for idx in self.sampler:
            batch.append(self.dataset[idx])
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch) if self.collate_fn else batch
                batch = []
        if batch and not self.drop_last:
            yield self.collate_fn(batch) if self.collate_fn else batch

    def __len__(self) -> int:
        n = len(self.sampler)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def state_dict(self) -> dict:
        return {
            "dataset": self.dataset.state_dict(),
            "sampler": self.sampler.state_dict() if self.sampler is not None else {},
        }

    def load_state_dict(self, state_dict: dict) -> None:
        self.dataset.load_state_dict(state_dict.get("dataset"))
        if self.sampler is not None:
            self.sampler.load_state_dict(state_dict.get("sampler"))


class DispatchingDataLoader:
    """Single-host-storage feed: ONLY process 0 owns a loader (and so reads the corpus);
    every other process passes ``local_loader=None`` and never touches storage.

    Per step, process 0 broadcasts a fixed-size int32 header (per-key dtype/shape, with a
    sentinel for exhaustion) and then the batch arrays; receivers contribute zero-filled
    placeholders of the header-announced shapes (``broadcast_one_to_all`` requires
    matching structures on all processes). All hosts then hold the full global batch and
    cut their devices' shards locally via ``make_array_from_callback`` — the
    dispatch-then-slice layout of the reference's `DispatchingDataLoader`
    (`data/dataloader.py:21-104`), with the NCCL broadcast replaced by an XLA device
    collective. Key names/order ride a one-time JSON schema broadcast.
    """

    _SCHEMA_BYTES = 4096
    _MAX_DIMS = 6
    # 0 = key is None; bfloat16 via ml_dtypes (host batches are normally integer tokens).
    # int64 is NOT here: broadcast_one_to_all silently downcasts it to int32 under JAX's
    # default x64-disabled mode — int64 batches are range-checked and cast to int32 on the
    # sending side (_header) instead, so header dtype and delivered dtype always agree.
    _DTYPES = [None, np.int32, np.float32, jax.numpy.bfloat16, np.bool_]

    def __init__(self, local_loader, mesh, batch_axes: tuple[str, ...] = ("dp", "fsdp")) -> None:
        from jax.sharding import NamedSharding, PartitionSpec

        assert (local_loader is not None) == (jax.process_index() == 0), (
            "process 0 must own the loader; every other process must pass None"
        )
        self.local_loader = local_loader
        self.mesh = mesh
        self.sharding = NamedSharding(mesh, PartitionSpec(batch_axes))
        self._keys: list[str] | None = None
        # length broadcast EAGERLY: __init__ runs on every process (a collective here can't
        # deadlock), and len() must be correct on receivers BEFORE the first epoch's schema
        # broadcast — callers size progress bars and schedules off it
        length = np.asarray(
            [len(local_loader) if local_loader is not None else 0], np.int32
        )
        self._length = int(np.asarray(self._broadcast(length))[0])

    # -------------------------------------------------------------- collective plumbing
    @staticmethod
    def _broadcast(tree):
        from jax.experimental import multihost_utils

        return multihost_utils.broadcast_one_to_all(tree)

    def _broadcast_schema(self, batch: dict | None) -> None:
        """One-time, piggybacked on the FIRST real batch (no throwaway batch is ever
        materialized): key order as a fixed-size byte buffer (length already rode the
        eager __init__ broadcast)."""
        if self._keys is not None:
            return
        if self.local_loader is not None:
            payload = json.dumps(
                {
                    # batch None = the source is empty; receivers then stop immediately
                    "keys": sorted(batch.keys()) if batch is not None else [],
                }
            )
            raw = payload.encode()
            assert len(raw) < self._SCHEMA_BYTES, "batch schema exceeds the schema buffer"
            buf = np.zeros(self._SCHEMA_BYTES, np.uint8)
            buf[: len(raw)] = np.frombuffer(raw, np.uint8)
        else:
            buf = np.zeros(self._SCHEMA_BYTES, np.uint8)
        buf = np.asarray(self._broadcast(buf))
        self._keys = json.loads(bytes(buf[buf != 0]).decode())["keys"]

    def _header(self, batch: dict | None) -> np.ndarray:
        """[n_keys, 1 + MAX_DIMS] int32: dtype code + padded shape; all -1 = exhausted.
        int32 on purpose: the collective downcasts int64 silently, so announce what is
        actually delivered."""
        h = np.full((len(self._keys), 1 + self._MAX_DIMS), -1, np.int32)
        if batch is not None:
            for row, key in enumerate(self._keys):
                value = batch.get(key)
                if value is None:
                    h[row, 0] = 0
                    continue
                value = np.asarray(value)
                if value.ndim > self._MAX_DIMS:
                    raise ValueError(
                        f"DispatchingDataLoader cannot broadcast batch key '{key}' with "
                        f"ndim {value.ndim}; the header carries at most {self._MAX_DIMS} "
                        "dims"
                    )
                if value.dtype == np.int64:
                    # the collective would silently downcast int64 -> int32 (x64 disabled);
                    # cast explicitly after proving no value is truncated
                    info = np.iinfo(np.int32)
                    if value.size and (value.min() < info.min or value.max() > info.max):
                        raise ValueError(
                            f"DispatchingDataLoader batch key '{key}' holds int64 values "
                            "outside int32 range; broadcast_one_to_all would truncate "
                            "them silently under x64-disabled JAX"
                        )
                    value = value.astype(np.int32)
                code = next(
                    (
                        i
                        for i, dt in enumerate(self._DTYPES)
                        if dt is not None and value.dtype == dt
                    ),
                    None,
                )
                if code is None:
                    raise ValueError(
                        f"DispatchingDataLoader cannot broadcast batch key '{key}' of "
                        f"dtype {value.dtype}; supported: "
                        f"{[np.dtype(dt).name for dt in self._DTYPES if dt is not None]}"
                    )
                h[row, 0] = code
                h[row, 1 : 1 + value.ndim] = value.shape
        return h

    # -------------------------------------------------------------- iteration
    def __iter__(self) -> Iterator:
        source = iter(self.local_loader) if self.local_loader is not None else None
        first = True
        while True:
            batch = next(source, None) if source is not None else None
            if first:
                self._broadcast_schema(batch)
                first = False
            header = np.asarray(self._broadcast(self._header(batch)))
            if (header < 0).all():  # source exhausted -> every process stops this epoch
                return
            payload = []
            for row, key in enumerate(self._keys):
                code = int(header[row, 0])
                if code <= 0:
                    continue
                shape = tuple(int(d) for d in header[row, 1:] if d >= 0)
                if batch is not None:
                    payload.append(np.asarray(batch[key], self._DTYPES[code]))
                else:
                    payload.append(np.zeros(shape, self._DTYPES[code]))
            payload = self._broadcast(tuple(payload))
            full = {}
            it = iter(payload)
            for row, key in enumerate(self._keys):
                code = int(header[row, 0])
                full[key] = None if code <= 0 else np.asarray(next(it))
            with get_telemetry().span("dataloader_assemble"):
                # one device_put per array: XLA slices each device's shard itself — same
                # placement as the per-key make_array_from_callback lambdas this replaces,
                # without one host callback per (key, device). Every process holds the
                # full broadcast batch, which is exactly device_put's multi-process
                # contract (same global value on all hosts).
                out = {
                    key: (
                        jax.device_put(value, self.sharding)
                        if value is not None
                        else None
                    )
                    for key, value in full.items()
                }
            get_telemetry().count("loader_batches")
            yield out

    def __len__(self) -> int:
        if self.local_loader is not None:
            return len(self.local_loader)  # live: may change after load_state_dict
        return self._length  # broadcast eagerly in __init__, valid before iteration

    def state_dict(self) -> dict:
        # only the reading process has loader state; checkpoint writes happen on process 0
        return self.local_loader.state_dict() if self.local_loader is not None else {}

    def load_state_dict(self, state_dict: dict) -> None:
        if self.local_loader is not None:
            self.local_loader.load_state_dict(state_dict)


class ShardedDataLoader:
    """Wraps a per-host dataloader; yields GLOBAL jax.Arrays sharded over the batch axes.

    Each host's loader yields its local [local_batch, ...] numpy batch;
    `make_array_from_process_local_data` forms the global array without any cross-host traffic.
    """

    def __init__(self, local_loader, mesh, batch_axes: tuple[str, ...] = ("dp", "fsdp")) -> None:
        from jax.sharding import NamedSharding, PartitionSpec

        self.local_loader = local_loader
        self.mesh = mesh
        self.sharding = NamedSharding(mesh, PartitionSpec(batch_axes))

    def __iter__(self) -> Iterator:
        for batch in self.local_loader:
            with get_telemetry().span("dataloader_assemble"):
                out = {
                    k: (
                        jax.make_array_from_process_local_data(self.sharding, np.asarray(v))
                        if v is not None
                        else None
                    )
                    for k, v in batch.items()
                }
            get_telemetry().count("loader_batches")
            yield out

    def __len__(self) -> int:
        return len(self.local_loader)

    def state_dict(self) -> dict:
        return self.local_loader.state_dict()

    def load_state_dict(self, state_dict: dict) -> None:
        self.local_loader.load_state_dict(state_dict)
