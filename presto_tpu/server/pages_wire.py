"""DCN page serialization: typed columnar buffers + compression + checksum.

Reference parity: ``PagesSerde`` — per-block typed encodings with LZ4
compression and an xxhash checksum on the exchange wire (SURVEY.md §2.5
"Serialization"). Here: raw little-endian typed buffers per column,
adaptively zlib-compressed (stdlib zlib — numpy buffers in, C deflate
underneath; buffers below a size floor or whose sample prefix
compresses poorly ship raw, flagged by a per-buffer ``enc`` header
field defaulting to ``"zlib"``), crc32-checksummed per buffer, with a
JSON header.

Frame layout::

    b"PTP1" | u32 header_len | header_json | buffer_0 | buffer_1 | ...

The header lists per-column metadata (name, type, validity, dictionary
values, buffer sizes and crc32s). Dictionary columns ship ids (int32)
plus their value list in the header — dictionaries are tiny relative to
id vectors, and shipping values keeps the wire self-contained across
processes that never shared a dictionary.
"""

from __future__ import annotations

import json
import struct
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from presto_tpu import types as T
from presto_tpu.connectors.tpch import DictColumn
from presto_tpu.exec.staging import MaskedColumn
from presto_tpu.server.protocol import decode as _decode_type
from presto_tpu.server.protocol import encode as _encode_type
from presto_tpu.utils import tracing

_MAGIC = b"PTP1"

#: adaptive compression floor: buffers below this ship raw — zlib
#: setup costs more than it saves on tiny buffers
MIN_COMPRESS_BYTES = 512

#: sample prefix compressed to probe compressibility of large buffers
COMPRESS_SAMPLE_BYTES = 4096

#: sample compressed/raw ratio above which the whole buffer ships raw
#: (already-compressed or high-entropy data: deflate would burn CPU on
#: both ends to GROW the payload)
COMPRESS_SAMPLE_RATIO = 0.9


def _encode_buffer(raw: bytes) -> Tuple[bytes, int, str]:
    """Adaptive wire encoding: ``(payload, crc32(raw), enc)`` where
    ``enc`` is ``"zlib"`` or ``"raw"``. Small buffers and buffers whose
    sample prefix compresses poorly skip zlib (metrics:
    ``exchange.compress_skipped``); compressed buffers record the bytes
    saved (``exchange.bytes_saved``). The header's per-buffer ``enc``
    field defaults to ``"zlib"`` when absent, so old frames decode
    unchanged (wire format stays PTP1).

    This is the ONE encoder behind both producer entry points —
    ``page_to_wire_columns`` (device-page serialization) and
    ``payload_to_wire_columns`` (the partitioned-output re-serialize
    path, which slices a page into many small per-partition buffers) —
    so the skip/saved counters read consistently whichever path
    produced the frame. Buffers below the size floor return BEFORE any
    probe logic: the 4KB ratio probe would compress a sample larger
    than the buffer itself, exactly the waste the floor exists to
    avoid."""
    from presto_tpu.utils.metrics import REGISTRY

    crc = zlib.crc32(raw)
    if len(raw) < MIN_COMPRESS_BYTES:
        REGISTRY.counter("exchange.compress_skipped").update()
        return raw, crc, "raw"
    if len(raw) > COMPRESS_SAMPLE_BYTES:
        sample = raw[:COMPRESS_SAMPLE_BYTES]
        ratio = len(zlib.compress(sample, 1)) / len(sample)
        if ratio > COMPRESS_SAMPLE_RATIO:
            REGISTRY.counter("exchange.compress_skipped").update()
            return raw, crc, "raw"
    comp = zlib.compress(raw, 1)
    if len(comp) < len(raw):
        REGISTRY.counter("exchange.bytes_saved").update(
            len(raw) - len(comp)
        )
        return comp, crc, "zlib"
    REGISTRY.counter("exchange.compress_skipped").update()
    return raw, crc, "raw"


def _decode_buffer(payload: bytes, enc: str) -> bytes:
    if enc == "raw":
        return bytes(payload)
    return zlib.decompress(payload)


def serialize_page(
    columns: List[Tuple[str, np.ndarray, Optional[np.ndarray], T.DataType,
                        Optional[tuple]]],
    nrows: int,
) -> bytes:
    """columns: (name, data[:n], valid[:n]|None, dtype, dict_values|None).

    Numeric data must already be in native representation (scaled ints
    for decimals, epoch days for dates, int32 ids for dictionary cols).
    """
    from presto_tpu.exec.staging import ArrayColumn

    header: Dict = {"nrows": nrows, "columns": []}
    buffers: List[bytes] = []
    for name, data, valid, dtype, dict_values in columns:
        if isinstance(data, ArrayColumn):
            # array column: offsets buffer + flat values buffer
            off = np.ascontiguousarray(
                np.asarray(data.offsets, np.int32)
            )
            vals = np.ascontiguousarray(
                np.asarray(data.values)[: int(off[-1]) if len(off) else 0]
            )
            oraw, vraw_ = off.tobytes(), vals.tobytes()
            ocomp, ocrc, oenc = _encode_buffer(oraw)
            vcomp_, vcrc_, venc = _encode_buffer(vraw_)
            col = {
                "name": name,
                "type": _encode_type(dtype),
                "array": True,
                "off_comp_size": len(ocomp),
                "off_raw_size": len(oraw),
                "off_crc32": ocrc,
                "off_enc": oenc,
                "np_dtype": vals.dtype.str,
                "comp_size": len(vcomp_),
                "raw_size": len(vraw_),
                "crc32": vcrc_,
                "enc": venc,
            }
            buffers.append(ocomp)
            buffers.append(vcomp_)
            if valid is not None:
                vraw = np.packbits(
                    np.asarray(valid, dtype=bool)
                ).tobytes()
                vc, vcr, vvenc = _encode_buffer(vraw)
                col["valid_comp_size"] = len(vc)
                col["valid_raw_size"] = len(vraw)
                col["valid_crc32"] = vcr
                col["valid_enc"] = vvenc
                buffers.append(vc)
            if dict_values is not None:
                col["dictionary"] = list(dict_values)
            header["columns"].append(col)
            continue
        data = np.ascontiguousarray(data)
        raw = data.tobytes()
        comp, crc, enc = _encode_buffer(raw)
        col: Dict = {
            "name": name,
            "type": _encode_type(dtype),
            "np_dtype": data.dtype.str,
            "comp_size": len(comp),
            "raw_size": len(raw),
            "crc32": crc,
            "enc": enc,
        }
        buffers.append(comp)
        if valid is not None:
            vraw = np.packbits(np.asarray(valid, dtype=bool)).tobytes()
            vcomp, vcrc, venc = _encode_buffer(vraw)
            col["valid_comp_size"] = len(vcomp)
            col["valid_raw_size"] = len(vraw)
            col["valid_crc32"] = vcrc
            col["valid_enc"] = venc
            buffers.append(vcomp)
        if dict_values is not None:
            col["dictionary"] = list(dict_values)
        header["columns"].append(col)
    hj = json.dumps(header).encode()
    return b"".join(
        [_MAGIC, struct.pack("<I", len(hj)), hj] + buffers
    )


def deserialize_page(buf: bytes):
    """-> (payload {name: ndarray | DictColumn}, schema {name: DataType},
    nrows) — feeds exec.staging.stage_page directly."""
    if buf[:4] != _MAGIC:
        raise ValueError("bad page frame magic")
    (hlen,) = struct.unpack_from("<I", buf, 4)
    header = json.loads(buf[8 : 8 + hlen].decode())
    off = 8 + hlen
    payload: Dict = {}
    schema: Dict[str, T.DataType] = {}
    nrows = header["nrows"]
    for col in header["columns"]:
        if col.get("array"):
            from presto_tpu.exec.staging import ArrayColumn

            ocomp = buf[off : off + col["off_comp_size"]]
            off += col["off_comp_size"]
            oraw = _decode_buffer(ocomp, col.get("off_enc", "zlib"))
            if zlib.crc32(oraw) != col["off_crc32"]:
                raise ValueError(
                    f"offsets checksum mismatch on {col['name']}"
                )
            offsets = np.frombuffer(oraw, np.int32).copy()
            vcomp2 = buf[off : off + col["comp_size"]]
            off += col["comp_size"]
            vraw2 = _decode_buffer(vcomp2, col.get("enc", "zlib"))
            if zlib.crc32(vraw2) != col["crc32"]:
                raise ValueError(
                    f"values checksum mismatch on {col['name']}"
                )
            values = np.frombuffer(
                vraw2, np.dtype(col["np_dtype"])
            ).copy()
            valid = None
            if "valid_comp_size" in col:
                vc = buf[off : off + col["valid_comp_size"]]
                off += col["valid_comp_size"]
                vr = _decode_buffer(vc, col.get("valid_enc", "zlib"))
                if zlib.crc32(vr) != col["valid_crc32"]:
                    raise ValueError(
                        f"validity checksum mismatch on {col['name']}"
                    )
                valid = np.unpackbits(
                    np.frombuffer(vr, np.uint8), count=nrows
                ).astype(bool)
            dtype = _decode_type(col["type"])
            schema[col["name"]] = dtype
            payload[col["name"]] = ArrayColumn(
                offsets=offsets,
                values=values,
                valid=valid,
                dict_values=(
                    tuple(col["dictionary"])
                    if "dictionary" in col
                    else None
                ),
            )
            continue
        comp = buf[off : off + col["comp_size"]]
        off += col["comp_size"]
        raw = _decode_buffer(comp, col.get("enc", "zlib"))
        if len(raw) != col["raw_size"] or zlib.crc32(raw) != col["crc32"]:
            raise ValueError(f"page checksum mismatch on {col['name']}")
        data = np.frombuffer(raw, dtype=np.dtype(col["np_dtype"])).copy()
        valid = None
        if "valid_comp_size" in col:
            vcomp = buf[off : off + col["valid_comp_size"]]
            off += col["valid_comp_size"]
            vraw = _decode_buffer(vcomp, col.get("valid_enc", "zlib"))
            if zlib.crc32(vraw) != col["valid_crc32"]:
                raise ValueError(
                    f"validity checksum mismatch on {col['name']}"
                )
            valid = np.unpackbits(
                np.frombuffer(vraw, dtype=np.uint8), count=nrows
            ).astype(bool)
        dtype = _decode_type(col["type"])
        schema[col["name"]] = dtype
        dict_values = (
            tuple(col["dictionary"]) if "dictionary" in col else None
        )
        if valid is not None:
            # native repr + mask: exact (no Python-value round trip)
            payload[col["name"]] = MaskedColumn(
                data=data, valid=valid, values=dict_values
            )
        elif dict_values is not None:
            payload[col["name"]] = DictColumn(
                ids=data.astype(np.int32), values=dict_values
            )
        else:
            payload[col["name"]] = data
    return payload, schema, nrows


def merge_payloads(
    payloads: List[tuple], schema: Dict[str, T.DataType]
) -> Dict[str, object]:
    """Merge deserialized wire pages ``(payload, schema, nrows)`` from
    many workers into ONE staging payload for ``stage_page``.

    Dictionary-encoded columns need id remapping: each worker built its
    dictionary from the values *it* saw, so id spaces differ across
    payloads. Dictionaries are sorted-unique by construction (order-
    preserving, see connectors.tpch.DictColumn), so the union dictionary
    is the sorted union of values and remapping is a searchsorted.
    """
    from presto_tpu.exec.staging import ArrayColumn

    out: Dict[str, object] = {}
    for name in schema:
        if schema[name].is_array:
            out[name] = _merge_array_parts(
                [p[name] for p, _s, _n in payloads]
            )
            continue
        parts = []  # (data, valid|None, dict_values|None) per payload
        for payload, _schema, nrows in payloads:
            col = payload[name]
            if isinstance(col, MaskedColumn):
                parts.append((col.data, col.valid, col.values))
            elif isinstance(col, DictColumn):
                parts.append((np.asarray(col.ids, np.int32), None,
                              tuple(col.values)))
            else:
                parts.append((np.asarray(col), None, None))
        has_dict = any(v is not None for _, _, v in parts)
        has_valid = any(v is not None for _, v, _ in parts)
        if has_dict:
            union = sorted(set().union(*[
                v if v is not None else () for _, _, v in parts
            ]))
            uarr = np.asarray(union, dtype=object)
            datas, valids = [], []
            for data, valid, values in parts:
                ids = np.asarray(data, np.int64)
                if values:
                    vals = np.asarray(values, dtype=object)
                    remap = np.searchsorted(uarr, vals).astype(np.int64)
                    # clip: padded/NULL slots may carry out-of-range ids
                    ids = remap[np.clip(ids, 0, len(vals) - 1)]
                datas.append(ids.astype(np.int32))
                valids.append(
                    valid
                    if valid is not None
                    else np.ones(len(ids), dtype=bool)
                )
            data = np.concatenate(datas) if datas else np.empty(0, np.int32)
            if has_valid:
                out[name] = MaskedColumn(
                    data=data,
                    valid=np.concatenate(valids),
                    values=tuple(union),
                )
            else:
                out[name] = DictColumn(ids=data, values=np.asarray(union))
        else:
            datas = [np.asarray(d) for d, _, _ in parts]
            data = (
                np.concatenate(datas)
                if datas
                else np.empty(0, schema[name].np_dtype)
            )
            if has_valid:
                valids = [
                    v if v is not None else np.ones(len(d), dtype=bool)
                    for d, v, _ in parts
                ]
                out[name] = MaskedColumn(
                    data=data, valid=np.concatenate(valids)
                )
            else:
                out[name] = data
    return out


def _merge_array_parts(parts: List) -> "object":
    """Concatenate ArrayColumn payload chunks: values concat + offsets
    rebase. String-element dictionaries must agree across chunks
    (cross-dictionary array remap is a guarded gap)."""
    from presto_tpu.exec.staging import ArrayColumn

    dicts = {p.dict_values for p in parts if p.dict_values is not None}
    if len(dicts) > 1:
        raise NotImplementedError(
            "merging array columns with differing element "
            "dictionaries is not supported"
        )
    offsets = [np.zeros(1, np.int32)]
    values = []
    valids = []
    base = 0
    any_valid = any(p.valid is not None for p in parts)
    for p in parts:
        off = np.asarray(p.offsets, np.int32)
        n = max(len(off) - 1, 0)
        offsets.append(off[1:] + base)
        base += int(off[-1]) if len(off) else 0
        values.append(np.asarray(p.values)[: int(off[-1]) if len(off) else 0])
        if any_valid:
            valids.append(
                np.asarray(p.valid, bool)
                if p.valid is not None
                else np.ones(n, bool)
            )
    return ArrayColumn(
        offsets=np.concatenate(offsets),
        values=(
            np.concatenate(values) if values else np.zeros(0)
        ),
        valid=np.concatenate(valids) if any_valid else None,
        dict_values=next(iter(dicts)) if dicts else None,
    )


def page_to_wire_columns(page, fetched_n: Optional[int] = None):
    """Device Page -> serialize_page input, with ONE batched device->host
    fetch (two-phase; see exec.host_ops for the relay rationale)."""
    import jax

    from presto_tpu.exec.staging import ArrayColumn

    with tracing.phase("fetch", site="wire"):
        n = fetched_n if fetched_n is not None else int(page.num_valid)
        leaves = []
        for blk in page.blocks:
            if blk.offsets is not None:
                # array block: offsets prefix + FULL flat values (live
                # extent is data-dependent; serialize trims to
                # offsets[-1])
                leaves.append(blk.offsets[: n + 1])
                leaves.append(blk.data)
            else:
                leaves.append(blk.data[:n])
            if blk.valid is not None:
                leaves.append(blk.valid[:n])
        fetched = jax.device_get(leaves)
    cols = []
    i = 0
    for name, blk in zip(page.names, page.blocks):
        if blk.offsets is not None:
            offsets = np.asarray(fetched[i])
            i += 1
            values = np.asarray(fetched[i])
            i += 1
            valid = None
            if blk.valid is not None:
                valid = fetched[i]
                i += 1
            cols.append(
                (
                    name,
                    ArrayColumn(offsets=offsets, values=values,
                                valid=valid),
                    valid,
                    blk.dtype,
                    (
                        tuple(blk.dictionary.values)
                        if blk.dictionary is not None
                        else None
                    ),
                )
            )
            continue
        data = fetched[i]
        i += 1
        valid = None
        if blk.valid is not None:
            valid = fetched[i]
            i += 1
        dict_values = None
        if blk.dictionary is not None:
            data, dict_values = _used_values(data, blk.dictionary.values)
        cols.append((name, data, valid, blk.dtype, dict_values))
    return cols, n


def _used_values(ids: np.ndarray, values: np.ndarray):
    """A dictionary column's ids and values cut to the values its rows
    use, where it uses fewer than the dictionary holds: a subset of a
    sorted dictionary is sorted, so the ids keep their order, and the
    page no longer carries (nor makes every reader rebuild) a
    dictionary of the whole table — TPC-H Q18's 100 result rows carry
    ``c_name``'s 1.5 M values at SF10 otherwise."""
    ids = np.asarray(ids)
    if len(ids) >= len(values):
        return ids, tuple(values)
    used, remap = np.unique(
        np.clip(ids.astype(np.int64), 0, max(len(values) - 1, 0)),
        return_inverse=True,
    )
    return remap.astype(ids.dtype).reshape(ids.shape), tuple(
        np.asarray(values)[used]
    )


def payload_to_wire_columns(payload, schema, nrows: int):
    """Staging payload (deserialize_page / streaming._page_to_payload
    form) -> serialize_page input. Used by the partitioned-output path:
    producers bucket host-side payloads and re-serialize each
    partition's slice without another device round trip."""
    from presto_tpu.connectors.tpch import DictColumn
    from presto_tpu.exec.staging import ArrayColumn, MaskedColumn

    cols = []
    for name, t in schema.items():
        col = payload[name]
        if isinstance(col, ArrayColumn):
            sliced = col[0:nrows]  # offsets rebase + values trim
            cols.append(
                (name, sliced, sliced.valid, t, sliced.dict_values)
            )
        elif isinstance(col, MaskedColumn):
            values = (
                tuple(col.values) if col.values is not None else None
            )
            cols.append(
                (
                    name,
                    np.asarray(col.data)[:nrows],
                    np.asarray(col.valid)[:nrows],
                    t,
                    values,
                )
            )
        elif isinstance(col, DictColumn):
            cols.append(
                (
                    name,
                    np.asarray(col.ids, np.int32)[:nrows],
                    None,
                    t,
                    tuple(col.values),
                )
            )
        else:
            cols.append((name, np.asarray(col)[:nrows], None, t, None))
    return cols
