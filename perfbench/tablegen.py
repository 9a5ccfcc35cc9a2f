"""The benchmark's own table: generated on the device from the seed.

The table is the paper's synthetic dataset (OLA-RAW, arXiv:1702.00358,
Section 7.1): column k is Zipf with parameter ``0.25 * k`` over a finite
rank support, each rank spread over the value domain as ``rank * vmax /
support``.  This is the definition of ``repro.data.generator.bounded_zipf``;
the benchmark keeps its own copy so that it owns the raw bytes it hands the
system, and makes them on the device (the host generator and encoder took
about 42 s for 2**23 tuples).

Two encodings, the same as ``repro.data.formats``:

* ``ascii``: 16-byte fixed-width fields ``sign, 8 integer digits, '.',
  6 fraction digits``;
* ``binary``: big-endian IEEE float32 (the FITS convention).

A value depends on its rank alone, so every encoded field is a function of
one integer: the ASCII digits are computed exactly in int32 arithmetic, and
the binary words come from a per-rank table of float32 values.
"""

from __future__ import annotations

import dataclasses

import numpy as np

INT_DIGITS = 8
FRAC_DIGITS = 6
FIELD_BYTES = 1 + INT_DIGITS + 1 + FRAC_DIGITS


@dataclasses.dataclass(frozen=True)
class TableSpec:
    """Shape of the synthetic table (a configuration's ``table`` block)."""

    num_tuples: int
    num_cols: int
    num_chunks: int
    zipf_support: int
    zipf_step: float
    value_max: float
    format: str

    @classmethod
    def from_dict(cls, d: dict) -> "TableSpec":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)})

    @property
    def chunk_tuples(self) -> int:
        if self.num_tuples % self.num_chunks:
            raise ValueError("num_tuples must be a multiple of num_chunks")
        return self.num_tuples // self.num_chunks

    @property
    def record_bytes(self) -> int:
        per = FIELD_BYTES if self.format == "ascii" else 4
        return self.num_cols * per

    @property
    def step(self) -> float:
        """Value distance between neighbouring ranks."""
        return self.value_max / self.zipf_support


def rank_values(spec: TableSpec) -> np.ndarray:
    """(support,) float64: the value each rank stands for, as generated
    (``bounded_zipf``: ``rank * (vmax / support)``)."""
    return np.arange(spec.zipf_support, dtype=np.float64) * spec.step


def zipf_cdfs(spec: TableSpec) -> np.ndarray:
    """(C, support) float64 CDFs of each column's rank distribution."""
    ranks = np.arange(1, spec.zipf_support + 1, dtype=np.float64)
    out = np.empty((spec.num_cols, spec.zipf_support))
    for k in range(spec.num_cols):
        w = ranks ** -(spec.zipf_step * k)
        c = np.cumsum(w)
        out[k] = c / c[-1]
    return out


def encode_ascii_ranks(ranks, value_max: float, support: int):
    """Ranks (..., C) int32 -> fixed-width ASCII bytes (..., C * 16) uint8.

    Traceable.  Exact for the generator's value grid: with ``vmax = 1e8 - 1``
    and ``support = 1e5`` the value of rank r > 0 is ``1000 r - r / 1e5``,
    whose integer part is ``1000 r - 1`` and whose 6 fraction digits are
    ``1e6 - 10 r``; rank 0 is 0.  Other grids are refused.
    """
    import jax.numpy as jnp

    if not (value_max == 1e8 - 1 and support == 100_000):
        raise ValueError("the exact ASCII encoding assumes vmax=1e8-1, "
                         "support=1e5")
    r = ranks.astype(jnp.int32)
    pos = r > 0
    ip = jnp.where(pos, 1000 * r - 1, 0)
    fp = jnp.where(pos, 1_000_000 - 10 * r, 0)
    digits = [jnp.full_like(r, ord("+"))]
    for d in range(INT_DIGITS):
        digits.append(ip // 10 ** (INT_DIGITS - 1 - d) % 10 + ord("0"))
    digits.append(jnp.full_like(r, ord(".")))
    for d in range(FRAC_DIGITS):
        digits.append(fp // 10 ** (FRAC_DIGITS - 1 - d) % 10 + ord("0"))
    out = jnp.stack(digits, axis=-1).astype(jnp.uint8)      # (..., C, 16)
    return out.reshape(out.shape[:-2] + (-1,))


def binary_word_table(spec: TableSpec) -> np.ndarray:
    """(support,) uint32: each rank's value as float32, byte-swapped so that
    a little-endian store of the word lays down big-endian bytes."""
    f = rank_values(spec).astype(np.float32)
    return f.view(np.uint32).byteswap()


def encode_binary_ranks(ranks, words):
    """Ranks (..., C) -> big-endian float32 bytes (..., C * 4) uint8."""
    import jax
    import jax.numpy as jnp

    w = jnp.take(words, ranks, axis=0)                       # (..., C) u32
    b = jax.lax.bitcast_convert_type(w, jnp.uint8)           # (..., C, 4)
    return b.reshape(b.shape[:-2] + (-1,))


def _block_fn(spec: TableSpec, rows: int):
    import jax
    import jax.numpy as jnp

    def gen(key, cdfs, words):
        keys = jax.random.split(key, spec.num_cols)
        u = jax.vmap(lambda k: jax.random.uniform(k, (rows,)))(keys)  # (C, R)
        # a sort-merge search: the default binary search is one gather per
        # step, slow on the TPU at 2**20 queries a column
        r = jax.vmap(lambda c, x: jnp.searchsorted(c, x, method="sort"))(
            cdfs, u)
        r = jnp.minimum(r, spec.zipf_support - 1).astype(jnp.int32).T
        if spec.format == "ascii":
            raw = encode_ascii_ranks(r, spec.value_max, spec.zipf_support)
        else:
            raw = encode_binary_ranks(r, words)
        return r, raw

    return jax.jit(gen)


@dataclasses.dataclass
class Table:
    """The generated table: per-chunk raw bytes and the ranks behind them."""

    spec: TableSpec
    chunks: list                 # num_chunks arrays (chunk_tuples, rec) uint8
    ranks: np.ndarray            # (T, C) int32


def seed_words(seed: int, n: int) -> np.ndarray:
    """``n`` uint32 words from any non-negative seed (wider than 32 bits)."""
    return np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)


def generate(spec: TableSpec, seed: int, block_chunks: int = 64) -> Table:
    """Make the table on the default device, block by block, and bring the
    bytes and ranks back to the host once."""
    import jax
    import jax.numpy as jnp

    ct = spec.chunk_tuples
    block_chunks = max(1, min(block_chunks, spec.num_chunks))
    while spec.num_chunks % block_chunks:
        block_chunks -= 1
    rows = block_chunks * ct
    fn = _block_fn(spec, rows)
    cdfs = jnp.asarray(zipf_cdfs(spec), jnp.float32)
    words = jnp.asarray(binary_word_table(spec))
    key = jax.random.key(int(seed_words(seed, 1)[0]))
    n_blocks = spec.num_chunks // block_chunks
    chunks, ranks = [], []
    # two blocks in flight: one computes while the other comes back
    pending = [fn(jax.random.fold_in(key, 0), cdfs, words)]
    for b in range(n_blocks):
        if b + 1 < n_blocks:
            pending.append(fn(jax.random.fold_in(key, b + 1), cdfs, words))
        r, raw = pending.pop(0)
        raw = np.asarray(raw)
        ranks.append(np.asarray(r))
        chunks.extend(raw[i * ct:(i + 1) * ct] for i in range(block_chunks))
    return Table(spec=spec, chunks=chunks, ranks=np.concatenate(ranks))


# ---------------------------------------------------------------- reader ---

_POW_INT = 10 ** np.arange(INT_DIGITS - 1, -1, -1, dtype=np.int64)
_POW_FRAC = 10 ** np.arange(FRAC_DIGITS - 1, -1, -1, dtype=np.int64)


def parse_ascii(raw: np.ndarray, num_cols: int) -> np.ndarray:
    """Fixed-width ASCII records (n, C * 16) uint8 -> (n, C) float64.

    The benchmark's own reader: digits to an exact integer count of
    millionths, then one division."""
    f = raw.reshape(raw.shape[0], num_cols, FIELD_BYTES).astype(np.int64)
    ok = ((f[..., 1:1 + INT_DIGITS] >= 48).all()
          and (f[..., 1:1 + INT_DIGITS] <= 57).all()
          and (f[..., 1 + INT_DIGITS] == ord(".")).all())
    if not ok:
        raise ValueError("malformed fixed-width ASCII record")
    ip = (f[..., 1:1 + INT_DIGITS] - 48) @ _POW_INT
    fp = (f[..., 2 + INT_DIGITS:] - 48) @ _POW_FRAC
    sign = np.where(f[..., 0] == ord("-"), -1.0, 1.0)
    return sign * ((ip * 10 ** FRAC_DIGITS + fp) / 1e6)


def parse_binary(raw: np.ndarray, num_cols: int) -> np.ndarray:
    """Big-endian float32 records (n, C * 4) uint8 -> (n, C) float64."""
    return np.ascontiguousarray(raw).view(">f4").reshape(
        raw.shape[0], num_cols).astype(np.float64)


def parse(spec: TableSpec, raw: np.ndarray) -> np.ndarray:
    if spec.format == "ascii":
        return parse_ascii(raw, spec.num_cols)
    return parse_binary(raw, spec.num_cols)


def stored_rank_values(spec: TableSpec) -> np.ndarray:
    """(support,) float64: each rank's value as the store holds it, read
    back through the reader from its own encoding."""
    import jax.numpy as jnp

    r = np.arange(spec.zipf_support, dtype=np.int32)[:, None]
    if spec.format == "ascii":
        raw = np.asarray(encode_ascii_ranks(jnp.asarray(r), spec.value_max,
                                            spec.zipf_support))
        return parse_ascii(raw, 1)[:, 0]
    raw = np.asarray(encode_binary_ranks(
        jnp.asarray(r), jnp.asarray(binary_word_table(spec))))
    return parse_binary(raw, 1)[:, 0]
