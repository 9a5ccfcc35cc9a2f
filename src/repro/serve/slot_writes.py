"""Slot writes staged on the serving host between two rounds.

Admitting a query gives its slot a statistics row, a ``stopped`` flag, a
slot-table row and (grouped) fresh group cells; releasing it clears the
table row and stops the slot; group discovery restarts a cell; the
scheduler rewrites the fairness weights and stops late slots.  Made one by
one, each of these is an eager device op (an indexed update, a host-to-
device copy, a device-to-host round trip), paid per admitted query on the
serving host's critical path.

:class:`SlotWrites` keeps them on the host instead, in full-size staging
arrays with a per-slot mask, and :func:`apply_slot_writes` — one jitted
update, ``where(mask, staged, old)`` — applies a whole step's writes just
before the next round is dispatched.  The slot table is small (S rows), so
it is a host numpy mirror uploaded once when it changed.  Host reads of the
staged fields between two rounds go through :meth:`SlotWrites.view`, which
lays the staged rows over host copies of the device rows, so every decision
sees what the eager sequence of writes would have left on the device.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import EngineState
from repro.core.queries import SlotTable, slot_row_values


class StagedRows(NamedTuple):
    """A step's staged writes to the slots' device state (leading dim S)."""

    row: np.ndarray       # (S,) bool — statistics row written
    m: np.ndarray         # (S, N) int32
    ysum: np.ndarray      # (S, N) stats dtype
    ysq: np.ndarray       # (S, N)
    psum: np.ndarray      # (S, N)
    stop: np.ndarray      # (S,) bool — stopped flag written
    stopped: np.ndarray   # (S,) bool
    restart: np.ndarray   # (S, G) bool — group cell restarted (zeroed)


class SlotRows(NamedTuple):
    """The device-state leaves the staged writes touch."""

    m: jax.Array
    ysum: jax.Array
    ysq: jax.Array
    psum: jax.Array
    stopped: jax.Array
    gm: jax.Array
    gys: jax.Array
    gyq: jax.Array
    gps: jax.Array

    @classmethod
    def of(cls, state: EngineState) -> "SlotRows":
        st = state.stats
        return cls(st.m, st.ysum, st.ysq, st.psum, state.stopped,
                   state.gm, state.gys, state.gyq, state.gps)

    def into(self, state: EngineState) -> EngineState:
        return state._replace(
            stats=state.stats._replace(m=self.m, ysum=self.ysum,
                                       ysq=self.ysq, psum=self.psum),
            stopped=self.stopped, gm=self.gm, gys=self.gys, gyq=self.gyq,
            gps=self.gps)


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_slot_writes(rows: SlotRows, w: StagedRows) -> SlotRows:
    """Every staged write in one device update.  Shapes depend only on the
    server's slot count, chunk count and group cells, so this compiles
    once per server shape; outputs keep the inputs' shardings (donated)."""
    r = w.row[:, None]
    g = w.restart[:, :, None]
    return SlotRows(
        m=jnp.where(r, w.m, rows.m), ysum=jnp.where(r, w.ysum, rows.ysum),
        ysq=jnp.where(r, w.ysq, rows.ysq),
        psum=jnp.where(r, w.psum, rows.psum),
        stopped=jnp.where(w.stop, w.stopped, rows.stopped),
        gm=jnp.where(g, 0, rows.gm), gys=jnp.where(g, 0, rows.gys),
        gyq=jnp.where(g, 0, rows.gyq), gps=jnp.where(g, 0, rows.gps))


def _set(a: np.ndarray, idx, value) -> np.ndarray:
    """A copy of ``a`` with ``a[idx] = value``: an uploaded array is never
    written in place."""
    a = np.array(a)
    a[idx] = value
    return a


class SlotWrites:
    """The server's between-round writes to slot state, staged on the host.

    ``staged`` and ``flushes`` are counters of the row writes staged and of
    the flushes that applied them."""

    def __init__(self, state: EngineState, table: SlotTable, staged,
                 flushes):
        s, n = state.stats.ysum.shape
        self._shape = (s, n, int(state.gm.shape[1]))
        self._dtype = state.stats.ysum.dtype
        self._device_table = table
        self.table = SlotTable(*(np.asarray(x) for x in table))
        self._table_dirty = False
        self._rows: Optional[StagedRows] = None
        self._staged, self._flushes = staged, flushes

    def _stage(self) -> StagedRows:
        self._staged.inc()
        if self._rows is None:
            s, n, g = self._shape
            self._rows = StagedRows(
                row=np.zeros(s, bool), m=np.zeros((s, n), np.int32),
                ysum=np.zeros((s, n), self._dtype),
                ysq=np.zeros((s, n), self._dtype),
                psum=np.zeros((s, n), self._dtype),
                stop=np.zeros(s, bool), stopped=np.zeros(s, bool),
                restart=np.zeros((s, g), bool))
        return self._rows

    # ------------------------------------------------------------ writes ----
    def slot(self, s: int, seed: Optional[dict]) -> int:
        """Slot ``s``'s statistics row from ``seed`` (zeros when None), and
        the slot running; returns the seeded tuple count."""
        w = self._stage()
        w.row[s] = True
        w.stop[s], w.stopped[s] = True, False
        if seed is None:
            for a in (w.m, w.ysum, w.ysq, w.psum):
                a[s] = 0
            return 0
        w.m[s] = np.asarray(seed["m"], np.int32)
        w.ysum[s] = np.asarray(seed["ysum"], self._dtype)
        w.ysq[s] = np.asarray(seed["ysq"], self._dtype)
        w.psum[s] = np.asarray(seed["psum"], self._dtype)
        return int(np.asarray(seed["m"]).sum())

    def stop(self, slots) -> None:
        """Stop the given slots (deadlines, the census, a release)."""
        w = self._stage()
        slots = list(slots)
        w.stop[slots] = True
        w.stopped[slots] = True

    def restart_groups(self, s: int, cells=None) -> None:
        """Zero slot ``s``'s group cells (all, or the given indices): a new
        occupant starts from empty cells, and promoting a value out of
        ``__other__`` restarts the spill cell."""
        if self._shape[2] == 0:
            return
        w = self._stage()
        w.restart[s, slice(None) if cells is None else list(cells)] = True

    def table_row(self, s: int, row: dict) -> None:
        """Slot ``s``'s table row from an :func:`encode_slot` row."""
        self._staged.inc()
        vals = slot_row_values(self.table, row)
        self.table = SlotTable(*(_set(getattr(self.table, f), s, vals[f])
                                 for f in SlotTable._fields))
        self._table_dirty = True

    def release(self, s: int) -> None:
        """Deactivate slot ``s`` and stop it.  Descriptors stay, so the
        final round's report for the slot stays readable; the fairness
        weight alone goes back to 1.0, so an inactive slot stays neutral
        (``repro.sched.fairness``) and no contended weight leaks to the
        row's next occupant."""
        self.table = self.table._replace(
            active=_set(self.table.active, s, False),
            weight=_set(self.table.weight, s, np.float32(1.0)))
        self._table_dirty = True
        self.stop([s])

    def groups(self, s: int, gval: np.ndarray, gact: np.ndarray) -> None:
        """Slot ``s``'s tracked group values and live-cell flags."""
        self._staged.inc()
        self.table = self.table._replace(
            gval=_set(self.table.gval, s, np.asarray(gval, np.float32)),
            gact=_set(self.table.gact, s, np.asarray(gact, np.float32)))
        self._table_dirty = True

    def weights(self, w: np.ndarray) -> None:
        """The scheduler's fairness weight vector."""
        self._staged.inc()
        self.table = self.table._replace(weight=np.array(w, np.float32))
        self._table_dirty = True

    def drop_chunks(self, quarantined: np.ndarray) -> None:
        """Zero the quarantined chunks' columns of the staged rows, as the
        quarantine write zeroed them on the device after the rows'
        writes."""
        w = self._rows
        if w is None or not w.row.any():
            return
        for a in (w.m, w.ysum, w.ysq, w.psum):
            a[:, np.asarray(quarantined, bool)] = 0

    # ------------------------------------------------------------- reads ----
    def view(self, state: EngineState) -> EngineState:
        """``state`` as the next round will see it: each field with a write
        staged (statistics rows, ``stopped``, group cells) as a host array
        with the staged rows laid over the device copy, the rest as they
        are.  A device leaf is read at most once between two rounds (a
        device array keeps its host copy)."""
        w = self._rows
        if w is None:
            return state
        out = {}
        if w.row.any():
            r = w.row[:, None]
            out["stats"] = state.stats._replace(**{
                k: np.where(r, getattr(w, k), np.asarray(getattr(
                    state.stats, k))) for k in ("m", "ysum", "ysq", "psum")})
        if w.stop.any():
            out["stopped"] = np.where(w.stop, w.stopped,
                                      np.asarray(state.stopped))
        if w.restart.any():
            g = w.restart[:, :, None]
            out.update({k: np.where(g, 0, np.asarray(getattr(state, k)))
                        for k in ("gm", "gys", "gyq", "gps")})
        return state._replace(**out)

    # ------------------------------------------------------------- flush ----
    def flush(self, state: EngineState) -> tuple[EngineState, SlotTable]:
        """Apply every staged write: ``(state, table)`` as the round takes
        them.  Nothing staged, nothing dispatched or copied."""
        if self._rows is None and not self._table_dirty:
            return state, self._device_table
        self._flushes.inc()
        if self._rows is not None:
            state = apply_slot_writes(SlotRows.of(state), self._rows
                                      ).into(state)
            self._rows = None
        if self._table_dirty:
            self._device_table = jax.device_put(self.table)
            self._table_dirty = False
        return state, self._device_table
