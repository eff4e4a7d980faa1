"""Seeded GLMix flagship data: Avro container files and the same rows as arrays.

Copied from benches/_flagship_data.py (the fixed-width block writer, the
schema, `FEATURE_SHARDS`, `COORDINATES`); the original is superseded
(PERF.md, Open questions). Added here: the widths are arguments read from
the configuration file, and `flagship_blocks` yields the drawn arrays so
the plain reference can rebuild exactly the rows a file holds without
reading the file through the program under test; and which user and item
each row belongs to is drawn from a fixed seed and put in an order the
seed gives, so every seed has the same rows per entity (the shapes of the
per-entity solves, hence the compiled programs and the work) with other
features, effects and labels.

Ground truth: fixed weights w, per-user u and per-item v effects; the
margin is Xf·w + Xu·u[user] + Xi·v[item].
"""
from __future__ import annotations

import numpy as np

from photon_tpu.data.avro_io import AvroBlockWriter

PATTERN_SEED = 20240924  # fixed: which user and item each row belongs to


def flagship_schema() -> dict:
    ntv = {"type": "record", "name": "NTVF", "fields": [
        {"name": "name", "type": "string"},
        {"name": "term", "type": "string"},
        {"name": "value", "type": "float"}]}
    return {"type": "record", "name": "FlagshipExampleAvro", "fields": [
        {"name": "response", "type": "double"},
        {"name": "userId", "type": "string"},
        {"name": "itemId", "type": "string"},
        {"name": "fixed", "type": {"type": "array", "items": ntv}},
        {"name": "u_re", "type": {"type": "array", "items": "NTVF"}},
        {"name": "i_re", "type": {"type": "array", "items": "NTVF"}},
    ]}


def _varint_zigzag(v: int) -> bytes:
    z = (v << 1) ^ (v >> 63)
    out = bytearray()
    while True:
        b = z & 0x7F
        z >>= 7
        if z:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _string(s: str) -> bytes:
    b = s.encode()
    return _varint_zigzag(len(b)) + b


def fixed_feature_name(j: int) -> str:
    return f"f{j:02d}"


def random_feature_name(j: int) -> str:
    return f"r{j}"


def user_key(i: int) -> str:
    return f"u{i:06d}"


def item_key(i: int) -> str:
    return f"i{i:05d}"


def _template(d_fixed: int, d_re: int):
    """(template row bytes, slot index arrays) for the fixed-width record:
    every per-row byte position is precomputed once."""
    buf = bytearray()
    slots = {}

    def mark(name, width):
        slots.setdefault(name, []).extend(range(len(buf), len(buf) + width))
        buf.extend(b"\x00" * width)

    mark("response", 8)
    buf += _varint_zigzag(7) + b"u"
    mark("uid", 6)
    buf += _varint_zigzag(6) + b"i"
    mark("iid", 5)
    # fixed bag: one array block of d_fixed entries, then end marker
    buf += _varint_zigzag(d_fixed)
    for j in range(d_fixed):
        buf += _string(fixed_feature_name(j)) + _varint_zigzag(0)
        mark("fv", 4)
    buf += _varint_zigzag(0)
    for bag in ("uv", "iv"):
        buf += _varint_zigzag(d_re)
        for j in range(d_re):
            buf += _string(random_feature_name(j)) + _varint_zigzag(0)
            mark(bag, 4)
        buf += _varint_zigzag(0)
    return (np.frombuffer(bytes(buf), np.uint8),
            {k: np.asarray(v, np.int64) for k, v in slots.items()})


def _digits(ids, width):
    """(n, width) ASCII digit bytes of integer ids, zero-padded."""
    cols = [(ids // 10 ** (width - 1 - k)) % 10 + 48 for k in range(width)]
    return np.stack(cols, axis=1).astype(np.uint8)


def planted_truth(users: int, items: int, d_fixed: int, d_re: int,
                  seed: int):
    rng = np.random.default_rng(seed)
    w = (rng.normal(size=d_fixed) * 0.3).astype(np.float32)
    u = rng.normal(size=(users, d_re)).astype(np.float32)
    v = rng.normal(size=(items, d_re)).astype(np.float32)
    return w, u, v


def flagship_blocks(n_rows: int, users: int, items: int, truth, seed: int,
                    rows_per_block: int = 32768):
    """Yield (Xf, Xu, Xi, uid, iid, y) block by block — the one stream of
    draws both the writer and the plain reference consume."""
    w, u, v = truth
    pattern = np.random.default_rng([PATTERN_SEED, n_rows])
    rng = np.random.default_rng(seed)
    order = rng.permutation(n_rows)  # the same rows in another order
    uids = pattern.integers(0, users, size=n_rows)[order]
    iids = pattern.integers(0, items, size=n_rows)[order]
    done = 0
    while done < n_rows:
        b = min(rows_per_block, n_rows - done)
        Xf = rng.normal(size=(b, w.shape[0])).astype(np.float32)
        Xu = rng.normal(size=(b, u.shape[1])).astype(np.float32)
        Xi = rng.normal(size=(b, v.shape[1])).astype(np.float32)
        uid, iid = uids[done:done + b], iids[done:done + b]
        margin = (Xf @ w + np.einsum("nd,nd->n", Xu, u[uid])
                  + np.einsum("nd,nd->n", Xi, v[iid]))
        y = (rng.uniform(size=b)
             < 1 / (1 + np.exp(-margin))).astype(np.float64)
        yield Xf, Xu, Xi, uid, iid, y
        done += b


def flagship_arrays(n_rows: int, users: int, items: int, truth, seed: int):
    """The rows `write_flagship_avro` writes for the same arguments, as six
    concatenated arrays."""
    cols = zip(*flagship_blocks(n_rows, users, items, truth, seed))
    return tuple(np.concatenate(c) for c in cols)


def write_flagship_avro(path, n_rows: int, users: int, items: int,
                        truth, seed: int, codec: str = "null") -> None:
    """Stream `n_rows` records to `path`, one numpy-filled container block
    at a time (bounded memory: one block's bytes + its feature draws)."""
    d_fixed, d_re = truth[0].shape[0], truth[1].shape[1]
    template, slots = _template(d_fixed, d_re)
    with AvroBlockWriter(path, flagship_schema(), codec=codec) as writer:
        for Xf, Xu, Xi, uid, iid, y in flagship_blocks(
                n_rows, users, items, truth, seed):
            b = y.shape[0]
            block = np.tile(template, (b, 1))
            block[:, slots["response"]] = y.astype("<f8").view(
                np.uint8).reshape(b, 8)
            block[:, slots["uid"]] = _digits(uid, 6)
            block[:, slots["iid"]] = _digits(iid, 5)
            block[:, slots["fv"]] = Xf.astype("<f4").view(
                np.uint8).reshape(b, 4 * d_fixed)
            block[:, slots["uv"]] = Xu.astype("<f4").view(
                np.uint8).reshape(b, 4 * d_re)
            block[:, slots["iv"]] = Xi.astype("<f4").view(
                np.uint8).reshape(b, 4 * d_re)
            writer.write_block(b, block.tobytes())


FEATURE_SHARDS = {
    "fixed": {"bags": ["fixed"], "has_intercept": True},
    "u_re": {"bags": ["u_re"], "has_intercept": False},
    "i_re": {"bags": ["i_re"], "has_intercept": False},
}
