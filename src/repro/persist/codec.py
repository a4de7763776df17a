"""Serialize compiled JAX executables for the persistent plan tier.

The fast path is native XLA executable serialization
(``jax.experimental.serialize_executable``): a ``jit(...).lower(...).compile()``
artifact round-trips to bytes and loads back in milliseconds with **no
re-tracing and no re-compilation** — measured two orders of magnitude faster
than a cold trace for the statements in this repo.  The flip side is that the
payload is a native artifact, so the store's runtime stamp (jax/jaxlib,
backend, device count) gates every load; a mismatch degrades to recompile.

The blob is a pickle of ``(payload, in_tree, out_tree, device_ids)``: the
first three exactly as returned by ``serialize_executable.serialize`` (the
two ``PyTreeDef``s are not part of the payload and pickle round-trips them
faithfully), the last the ids of the devices the executable ran on.  Loading
pins execution to those devices: left to its default, the loader assigns
*every* local device, so a one-device program saved in a multi-device
process would expect one argument shard per device.  Host-side row metadata
(dictionary-encoded output vocabularies, trace-time stats) travels in the
JSON entry header via :func:`encode_dicts`/:func:`decode_dicts` so a warm
load can rebuild ``QueryResult`` decoding state without tracing.
"""
from __future__ import annotations

import pickle
from typing import Any, Callable, Mapping

import jax
from jax.experimental import serialize_executable as _se

from repro.tables.table import DictEncoding


def pack_compiled(compiled: Any) -> bytes:
    """Serialize a ``jax.stages.Compiled`` to an opaque blob."""
    payload, in_tree, out_tree = _se.serialize(compiled)
    shardings = jax.tree.leaves((compiled.input_shardings,
                                 compiled.output_shardings))
    device_ids = sorted({d.id for s in shardings for d in s.device_set})
    return pickle.dumps((payload, in_tree, out_tree, device_ids),
                        protocol=pickle.HIGHEST_PROTOCOL)


def load_compiled(blob: bytes) -> Callable:
    """Rehydrate a callable executable from :func:`pack_compiled` bytes.
    Raises ``KeyError`` when a device it ran on is absent here."""
    payload, in_tree, out_tree, device_ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return _se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


def encode_dicts(out_dicts: Mapping[str, DictEncoding | None] | None) -> dict | None:
    """Output dictionaries -> JSON-safe ``{column: vocab-list-or-None}``."""
    if out_dicts is None:
        return None
    return {
        name: (list(enc.vocab) if enc is not None else None)
        for name, enc in out_dicts.items()
    }


def decode_dicts(encoded: Mapping[str, list | None] | None) -> dict | None:
    """Inverse of :func:`encode_dicts`."""
    if encoded is None:
        return None
    return {
        name: (DictEncoding(vocab) if vocab is not None else None)
        for name, vocab in encoded.items()
    }


def jsonable_stats(stats: Mapping[str, Any] | None) -> dict:
    """Copy trace-time stats, keeping only JSON-representable scalars."""
    out = {}
    for k, v in (stats or {}).items():
        if isinstance(v, (str, int, float, bool)) or v is None:
            out[k] = v
        elif isinstance(v, (list, tuple)):
            out[k] = [x for x in v if isinstance(x, (str, int, float, bool))]
    return out
