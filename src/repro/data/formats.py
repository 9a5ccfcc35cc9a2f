"""Raw record formats and their codecs.

Two families, mirroring the paper's ptf-csv (text) and ptf-fits (binary):

* :class:`AsciiFixedFormat` — fixed-width ASCII decimal.  Each field is 16
  bytes: ``sign, 8 integer digits, '.', 6 fraction digits``; a record is the
  concatenation of its fields.  This is the *TPU adaptation* of CSV (see
  DESIGN.md §3): variable-width tokenization is inherently sequential, so the
  layout is regularised while keeping EXTRACT genuinely expensive (dozens of
  VPU ops per field — digit gathers, multiplies, adds — exactly the
  CPU-bound EXTRACT profile of the paper's text experiments).
* :class:`BinaryBigEndianFormat` — FITS stores big-endian IEEE floats; EXTRACT
  is a byte-swap + bitcast, i.e. nearly free.  This reproduces the paper's
  finding that ptf-fits processing is IO-bound while ptf-csv is CPU-bound.

Each format implements ``encode`` (host numpy, used by the generators),
``decode_ref`` (pure-jnp oracle, consumed by XLA on CPU and by kernel tests)
and exposes geometry used by the Pallas kernels' BlockSpecs.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

INT_DIGITS = 8
FRAC_DIGITS = 6
FIELD_BYTES = 1 + INT_DIGITS + 1 + FRAC_DIGITS  # sign + digits + '.' + digits
_MAX_ABS = 10.0 ** INT_DIGITS


@dataclasses.dataclass(frozen=True)
class AsciiFixedFormat:
    """Fixed-width ASCII decimal records (text family)."""

    num_cols: int
    name: str = "ascii"

    @property
    def record_bytes(self) -> int:
        return self.num_cols * FIELD_BYTES

    # -- host-side encode ---------------------------------------------------
    def encode(self, values: np.ndarray) -> np.ndarray:
        """(T, C) float -> (T, record_bytes) uint8."""
        t, c = values.shape
        assert c == self.num_cols, (c, self.num_cols)
        v = np.asarray(values, np.float64)
        if np.any(np.abs(v) >= _MAX_ABS):
            raise ValueError(f"values must be < 1e{INT_DIGITS} in magnitude")
        sign = np.where(v < 0, ord("-"), ord("+")).astype(np.uint8)
        av = np.abs(v)
        ip = np.floor(av)
        fp = np.rint((av - ip) * 10 ** FRAC_DIGITS).astype(np.int64)
        # carry from rounding .999999x up
        carry = fp >= 10 ** FRAC_DIGITS
        ip = ip.astype(np.int64) + carry
        fp = np.where(carry, 0, fp)
        out = np.empty((t, c, FIELD_BYTES), np.uint8)
        out[..., 0] = sign
        rem = ip
        for d in range(INT_DIGITS):  # most-significant first
            div = 10 ** (INT_DIGITS - 1 - d)
            out[..., 1 + d] = (rem // div % 10 + ord("0")).astype(np.uint8)
        out[..., 1 + INT_DIGITS] = ord(".")
        rem = fp
        for d in range(FRAC_DIGITS):
            div = 10 ** (FRAC_DIGITS - 1 - d)
            out[..., 2 + INT_DIGITS + d] = (rem // div % 10 + ord("0")).astype(np.uint8)
        return out.reshape(t, self.record_bytes)

    # -- device-side decode (oracle; the Pallas kernel mirrors this) --------
    def decode_ref(self, raw: jnp.ndarray) -> jnp.ndarray:
        """(T, record_bytes) uint8 -> (T, C) float32.  Pure jnp."""
        t = raw.shape[0]
        f = raw.reshape(t, self.num_cols, FIELD_BYTES).astype(jnp.int32)
        zero = jnp.int32(ord("0"))
        ipow = jnp.asarray([10 ** (INT_DIGITS - 1 - d) for d in range(INT_DIGITS)],
                           jnp.float32)
        fpow = jnp.asarray([10.0 ** -(d + 1) for d in range(FRAC_DIGITS)], jnp.float32)
        idig = (f[..., 1:1 + INT_DIGITS] - zero).astype(jnp.float32)
        fdig = (f[..., 2 + INT_DIGITS:] - zero).astype(jnp.float32)
        # full f32 precision: XLA:TPU's default rounds matmul operands to bf16
        hi = jax.lax.Precision.HIGHEST
        ival = jnp.einsum("tcd,d->tc", idig, ipow, precision=hi)
        fval = jnp.einsum("tcd,d->tc", fdig, fpow, precision=hi)
        sign = jnp.where(f[..., 0] == ord("-"), -1.0, 1.0).astype(jnp.float32)
        return sign * (ival + fval)

    def extract_cost_per_tuple(self) -> float:
        """Modeled op count per tuple — feeds the resource monitor's cost
        model (Section 5.4's CPU term).  Calibrated so ASCII extraction is
        CPU-bound against the default 565 MB/s read rate, matching the
        paper's ptf-csv characterization (tokenize+branch+convert dominate
        real text parsing, not the 3-op/digit arithmetic floor)."""
        return float(self.num_cols * (INT_DIGITS + FRAC_DIGITS) * 30)


@dataclasses.dataclass(frozen=True)
class BinaryBigEndianFormat:
    """Big-endian float32 records (FITS-like binary family)."""

    num_cols: int
    name: str = "binary"

    @property
    def record_bytes(self) -> int:
        return self.num_cols * 4

    def encode(self, values: np.ndarray) -> np.ndarray:
        v = np.asarray(values, ">f4")  # big-endian on purpose (FITS convention)
        return v.view(np.uint8).reshape(values.shape[0], self.record_bytes)

    def decode_ref(self, raw: jnp.ndarray) -> jnp.ndarray:
        t = raw.shape[0]
        b = raw.reshape(t, self.num_cols, 4).astype(jnp.uint32)
        word = (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]
        return jax.lax.bitcast_convert_type(word, jnp.float32)

    def extract_cost_per_tuple(self) -> float:
        return float(self.num_cols * 4)  # byte shuffles only: near-free


FORMATS = {"ascii": AsciiFixedFormat, "binary": BinaryBigEndianFormat}
