"""A surviving cell passes the client in whole strokes.

A unit stream's `ReadChunks` answer is received straight into its column
of the decode batch where the transport offers `read_chunks_into` (the
native datapath scatters the response over the rows; gRPC and the
in-process client copy into them), and a degraded read assembles the key
one run of units at a time. Held here over the real native datapath
(every datanode behind its gRPC server and its C++ listener), over a
scripted listener that dribbles, breaks and refuses, and, for every
erasure pattern, against the cell-by-cell assembly.
"""

import itertools
import json
import socket
import threading
import time
import types

import numpy as np
import pytest

from ozone_tpu.client import native_dn, resilience
from ozone_tpu.client.dn_client import DatanodeClientFactory
from ozone_tpu.client.ec_reader import OPS
from ozone_tpu.client.native_dn import NativeDatanodeClient
from ozone_tpu.codec.api import CoderOptions
from ozone_tpu.net.dn_service import DatanodeGrpcService
from ozone_tpu.net.rpc import RpcServer
from ozone_tpu.storage.fast_datapath import DatapathSidecar, load_lib
from ozone_tpu.storage.ids import BlockID, ChunkInfo, StorageError
from ozone_tpu.utils.checksum import Checksum, ChecksumType
from tests.test_ec_pipeline import CELL, MiniEC, _write_key
from tests.test_ec_read_once import _lose
from tests.test_read_buffers_recycled import (  # noqa: F401 - a fixture
    POISON,
    _assert_repair_exact,
    poisoned,
)

native = pytest.mark.skipif(load_lib() is None, reason="no native toolchain")

SCHEMES = {
    "rs-6-3": CoderOptions(6, 3, "rs", cell_size=CELL),
    "rs-10-4": CoderOptions(10, 4, "rs", cell_size=CELL),
    "lrc-12-2-2": CoderOptions(12, 4, "lrc", cell_size=CELL, local_groups=2),
}
STRIPES = 8  # one decode batch


class NativeMiniEC(MiniEC):
    """MiniEC whose datanodes are reached as a daemon's are: control
    verbs over gRPC, bulk verbs over the native listener."""

    def __init__(self, tmp_path, n_dn, opts):
        super().__init__(tmp_path, n_dn=n_dn, opts=opts)
        self.clients = DatanodeClientFactory()
        self._served = []
        for dn in self.dns:
            server = RpcServer()
            sidecar = DatapathSidecar(dn)
            assert sidecar.start() is not None
            DatanodeGrpcService(dn, server, datapath_port=sidecar.advertise)
            server.start()
            self._served.append((server, sidecar))
            self.clients.register_remote(dn.id, server.address)

    def close(self):
        self.clients.close()
        for server, sidecar in self._served:
            sidecar.stop()
            server.stop()
        super().close()


def _group(tmp_path, opts, transport="local", stripes=STRIPES, tail=0,
           seed=0):
    """One block group of `stripes` whole stripes and `tail` bytes."""
    make = NativeMiniEC if transport == "native" else MiniEC
    cluster = make(tmp_path, n_dn=opts.all_units + 1, opts=opts)
    cluster.clients.health = resilience.HealthRegistry(hedge_floor_s=30.0)
    data = np.random.default_rng(seed).integers(
        0, 256, stripes * opts.data_units * CELL + tail, dtype=np.uint8)
    (g,) = _write_key(cluster, data, block_size=(stripes + 1) * CELL)
    return cluster, g, data


COUNTERS = ("fill_cells", "fill_strokes", "survivor_cells_in_place",
            "assemble_cells", "assemble_strokes")


def _ops() -> dict:
    return {n: OPS.counter(n).value for n in COUNTERS}


def _delta(before: dict) -> dict:
    return {n: v - before[n] for n, v in _ops().items()}


# ------------------------------------ the in-place receive, the real daemon
@native
@pytest.mark.parametrize("lost", [(1,), (0, 3)], ids=["1lost", "2lost"])
@pytest.mark.parametrize("scheme", ["rs-6-3", "rs-10-4"])
def test_degraded_read_and_repair_receive_in_place_over_poison(
        tmp_path, poisoned, scheme, lost):
    """11 stripes: two decode batches, every pool page poisoned at its
    lease. Every surviving cell is received where the decoder reads it,
    a unit stream at a time, and the read and the repair are exact."""
    opts = SCHEMES[scheme]
    cluster, g, data = _group(tmp_path, opts, "native", stripes=11,
                              seed=opts.data_units)
    try:
        _lose(cluster, g, lost)
        for _ in range(2):  # the second read's buffers are recycled
            before = _ops()
            got = cluster.reader(g).read_all()
            assert np.array_equal(got, data)
            del got
            d = _delta(before)
            assert d["fill_cells"] == 11 * opts.data_units
            assert d["survivor_cells_in_place"] == d["fill_cells"]
            assert d["fill_strokes"] == 2 * opts.data_units
        before = _ops()
        _assert_repair_exact(opts, data, 11, list(lost), list(
            cluster.reader(g).recover_cells_iter(list(lost))))
        d = _delta(before)
        assert d["survivor_cells_in_place"] == d["fill_cells"] > 0
        assert d["assemble_cells"] == 0  # a repair assembles no key
    finally:
        cluster.close()


@native
def test_a_short_last_cell_is_exact_and_its_rows_tail_zero(tmp_path,
                                                           poisoned):
    """The last stripe holds one whole cell and 17 bytes of the second:
    the 17 are received in place at the row's start and the rest of the
    row, which held poison, is zeroed before the decoder sees it."""
    opts = SCHEMES["rs-6-3"]
    cluster, g, data = _group(tmp_path, opts, "native", stripes=3,
                              tail=CELL + 17, seed=3)
    seen = []

    def on_survivors(sb, valid, batch):
        bi, vi = list(sb).index(3), valid.index(1)
        seen.append(batch[bi, vi].copy())

    try:
        _lose(cluster, g, (0,))
        before = _ops()
        got = cluster.reader(g).read_all()
        assert np.array_equal(got, data)
        d = _delta(before)
        # unit 1's short cell came in place too; units 2-5 have no
        # fourth cell, and their zero rows are copies
        assert d["survivor_cells_in_place"] == 6 * 3 + 1 + 1
        assert d["fill_cells"] == 6 * 4
        _assert_repair_exact(opts, data, 4, [0], list(
            cluster.reader(g).recover_cells_iter(
                [0], on_survivors=on_survivors)))
        (row,) = seen
        assert np.array_equal(row[:17], data[-17:])
        assert not row[17:].any()
    finally:
        cluster.close()


@native
def test_a_stream_that_breaks_after_its_first_frame_is_replanned_around(
        tmp_path, poisoned):
    """A survivor's second cell is corrupt on disk: the daemon sends the
    first DATA frame and then a STATUS where the second was due. The
    half-received column is assigned again cell by cell, the second
    cell fails its read, the plan goes around the unit, and the answer
    is exact."""
    opts = SCHEMES["rs-6-3"]
    cluster, g, data = _group(tmp_path, opts, "native", seed=4)
    try:
        _lose(cluster, g, (0,))
        dn = next(d for d in cluster.dns if d.id == g.pipeline.nodes[2])
        path = dn.get_container(g.container_id).chunks.block_path(g.block_id)
        raw = bytearray(path.read_bytes())
        raw[CELL + 100] ^= 0xFF
        path.write_bytes(bytes(raw))
        # the transport's own answer: the daemon's error, its message
        # put together from where the scatter left it (a row's start)
        bd = dn.get_block(g.block_id)
        rows = [np.full(CELL, POISON, np.uint8) for _ in bd.chunks]
        with pytest.raises(StorageError) as ei:
            cluster.clients.get(dn.id).read_chunks_into(
                g.block_id, bd.chunks, rows, verify=True)
        assert ei.value.code == "CHECKSUM_MISMATCH"
        assert "slice" in str(ei.value)
        assert np.array_equal(rows[0], data.reshape(-1, 6, CELL)[0, 2])
        r = cluster.reader(g)
        got = r.read_all()
        assert 2 in r._failed
        assert np.array_equal(got, data)
    finally:
        cluster.close()


class _ReadChunksAlone:
    """A client that offers `read_chunks` and not `read_chunks_into`,
    handing every other name on to the client it wraps: as the fakes of
    the older tests do."""

    def __init__(self, inner):
        self._inner = inner
        self.dn_id = inner.dn_id
        self.batched = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def read_chunks(self, block_id, infos, verify=False):
        self.batched += 1
        return self._inner.read_chunks(block_id, infos, verify)


@native
def test_a_client_that_offers_read_chunks_alone_is_read_by_copy(tmp_path,
                                                                poisoned):
    opts = SCHEMES["rs-6-3"]
    cluster, g, data = _group(tmp_path, opts, "native", seed=5)
    try:
        _lose(cluster, g, (1,))
        wrapped = []
        for dn_id in g.pipeline.nodes[2:4]:
            w = _ReadChunksAlone(cluster.clients.get(dn_id))
            cluster.clients._remote[dn_id] = w
            wrapped.append(w)
        before = _ops()
        got = cluster.reader(g).read_all()
        assert np.array_equal(got, data)
        d = _delta(before)
        # its read went through ITS `read_chunks`, one call a stream,
        # and its cells were copied, a statement each
        assert [w.batched for w in wrapped] == [1, 1]
        assert d["fill_cells"] == 6 * STRIPES
        assert d["survivor_cells_in_place"] == 4 * STRIPES
        assert d["fill_strokes"] == 4 + 2 * STRIPES
    finally:
        cluster.close()


# ------------------------------------------- the scatter receive, scripted
_FRAME = native_dn._FRAME


def _frame(tag: int, body: bytes) -> bytes:
    return _FRAME.pack(len(body), tag) + body


class _ScriptedDatapath:
    """A listener that speaks the read half of the native protocol by a
    script: it takes a request up to its END frame, then writes
    `response` in pieces of `pieces` bytes (in turn, over and over), a
    pause after each, and closes once `close_after` bytes are out."""

    def __init__(self, response: bytes, pieces=(1 << 20,), pause_s=0.0,
                 close_after=None):
        self.response, self.pieces = response, pieces
        self.pause_s, self.close_after = pause_s, close_after
        self._lsock = socket.create_server(("127.0.0.1", 0))
        self.port = self._lsock.getsockname()[1]
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self):
        try:
            conn, _ = self._lsock.accept()
        except OSError:
            return
        with conn:
            buf = b""
            while True:  # to the END frame (tag 0x03, empty)
                while len(buf) < 5:
                    buf += conn.recv(65536)
                n, tag = _FRAME.unpack(buf[:5])
                while len(buf) < 5 + n:
                    buf += conn.recv(65536)
                buf = buf[5 + n:]
                if tag == native_dn._T_END:
                    break
            out = self.response if self.close_after is None \
                else self.response[:self.close_after]
            pos = 0
            for size in itertools.cycle(self.pieces):
                if pos >= len(out):
                    break
                conn.sendall(out[pos:pos + size])
                pos += size
                if self.pause_s:
                    time.sleep(self.pause_s)

    def client(self, rcvbuf=None) -> NativeDatanodeClient:
        c = NativeDatanodeClient("dn0", "127.0.0.1:1")
        c._np_probed, c._np_port = True, self.port
        if rcvbuf is not None:
            conn = native_dn._Conn("127.0.0.1", self.port)
            conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
            c._pool.append(conn)
        return c

    def close(self):
        self._lsock.close()
        self._thread.join(timeout=10)
        assert not self._thread.is_alive()


def _chunks(lengths, seed=0):
    rng = np.random.default_rng(seed)
    payloads = [rng.integers(0, 256, n, dtype=np.uint8) for n in lengths]
    infos, off = [], 0
    for j, p in enumerate(payloads):
        infos.append(ChunkInfo(f"c{j}", off, p.size,
                               Checksum(ChecksumType.NONE).compute(p)))
        off += CELL
    return infos, payloads


def _ok_stream(payloads, status=b"{}") -> bytes:
    return b"".join(_frame(native_dn._T_DATA, p.tobytes())
                    for p in payloads) + _frame(native_dn._T_STATUS, status)


@pytest.mark.parametrize("pieces, rcvbuf", [
    ((1,), None), ((2, 3, 5, 7, 11, 4093), 2048), ((4099, 1), 2048),
    ((1 << 20,), None)], ids=["bytewise", "primes", "over_a_cell", "whole"])
def test_a_scatter_receive_fed_in_dribbles_parses_every_frame(pieces,
                                                              rcvbuf):
    """Whole cells, a short one, an empty one, and a STATUS body larger
    than its scratch, written a few bytes at a time into a small
    receive buffer: frame heads and payloads straddle every boundary of
    the scatter list, each payload ends in its row, a short row's tail
    is not touched, and the connection is pooled again."""
    lengths = [4096, 4096, 0, 17, 4096] if pieces != (1,) \
        else [64, 0, 17, 64]
    infos, payloads = _chunks(lengths, seed=len(pieces))
    status = json.dumps({"pad": "x" * 700}).encode()
    server = _ScriptedDatapath(_ok_stream(payloads, status), pieces,
                               pause_s=0.0005 if rcvbuf else 0.0)
    c = server.client(rcvbuf)
    try:
        rows = [np.full(4096, POISON, np.uint8) for _ in infos]
        assert c.read_chunks_into(BlockID(1, 1), infos, rows) == len(infos)
        for row, p in zip(rows, payloads):
            assert np.array_equal(row[:p.size], p)
            assert (row[p.size:] == POISON).all()
        assert len(c._pool) == 1
    finally:
        c.close()
        server.close()


def test_an_error_where_a_frame_was_due_is_raised_whole_and_at_once():
    """The daemon's error after the first DATA frame: a STATUS whose
    body (longer than a frame head, so it lands in the next row and
    runs on into the socket) is put together again, and the call
    returns long before its IO timeout."""
    infos, payloads = _chunks([4096, 4096, 4096], seed=9)
    err = json.dumps({"error": {"code": "CHECKSUM_MISMATCH",
                                "message": "m" * 5000}}).encode()
    stream = _frame(native_dn._T_DATA, payloads[0].tobytes()) \
        + _frame(native_dn._T_STATUS, err)
    server = _ScriptedDatapath(stream, pieces=(4096 + 5 + 5 + 100, 1 << 20),
                               pause_s=0.05)
    c = server.client()
    try:
        rows = [np.full(4096, POISON, np.uint8) for _ in infos]
        t0 = time.monotonic()
        with pytest.raises(StorageError) as ei:
            c.read_chunks_into(BlockID(1, 1), infos, rows)
        assert time.monotonic() - t0 < 5
        assert ei.value.code == "CHECKSUM_MISMATCH"
        assert ei.value.msg == "m" * 5000
        assert np.array_equal(rows[0], payloads[0])
        assert not c._pool  # framing state unknown: not pooled
    finally:
        c.close()
        server.close()


@pytest.mark.parametrize("close_after", [3, 5 + 100, 5 + 4096 + 5 + 4096],
                         ids=["in_a_head", "in_a_payload", "before_status"])
def test_a_stream_the_peer_closes_is_the_peers_fault(close_after):
    infos, payloads = _chunks([4096, 4096], seed=11)
    server = _ScriptedDatapath(_ok_stream(payloads), close_after=close_after)
    c = server.client()
    try:
        rows = [np.full(4096, POISON, np.uint8) for _ in infos]
        with pytest.raises(StorageError) as ei:
            c.read_chunks_into(BlockID(1, 1), infos, rows)
        assert ei.value.code == "UNAVAILABLE"
        assert not c._pool
    finally:
        c.close()
        server.close()


def test_a_row_the_socket_cannot_write_is_refused_before_a_frame_leaves():
    infos, _ = _chunks([4096, 4096])
    c = NativeDatanodeClient("dn0", "127.0.0.1:1")
    c._np_probed, c._np_port = True, 1  # nobody listens: never dialled
    frozen = np.zeros(4096, np.uint8)
    frozen.flags.writeable = False
    try:
        for bad in (np.zeros(4095, np.uint8), np.zeros(4096, np.int8),
                    np.zeros(8192, np.uint8)[::2], frozen):
            with pytest.raises(ValueError):
                c.read_chunks_into(BlockID(1, 1), infos,
                                   [np.zeros(4096, np.uint8), bad])
    finally:
        c.close()


# ------------------------------------------------------------- the strokes
def _per_cell_put_cells(self, out, offset, length, sb, cols, src):
    """The assembly as it was: `_put_cell` once a cell."""
    copied = cells = 0
    for bi, s in enumerate(sb):
        for ci, u in cols:
            n = self._put_cell(out, offset, length, u, s, src[bi, ci])
            copied += n
            cells += bool(n)
    return copied, cells, cells


def _patterns():
    """Every pattern of up to p lost data units at k=6, one at k=10, one
    local repair at LRC (read set of width 6); the real datapath for a
    few of them."""
    out = []
    for e in (1, 2, 3):
        for lost in itertools.combinations(range(6), e):
            out.append(("rs-6-3", lost, "local"))
    out += [("rs-10-4", (2, 7), "local"), ("lrc-12-2-2", (4,), "local")]
    out += [pytest.param(s, lost, "native", marks=native)
            for s, lost in (("rs-6-3", (1,)), ("rs-6-3", (0, 3, 5)),
                            ("rs-10-4", (2, 7)), ("lrc-12-2-2", (4,)))]
    return out


def _pattern_id(v):
    return "".join(map(str, v)) if isinstance(v, tuple) else str(v)


@pytest.mark.parametrize("shape", ["whole", "ranged"])
@pytest.mark.parametrize("scheme, lost, transport", _patterns(),
                         ids=_pattern_id)
def test_a_degraded_read_assembles_in_strokes(tmp_path, poisoned, scheme,
                                              lost, transport, shape):
    """The key, or a range that cuts its first and last stripe, read
    with data units lost: equal byte for byte to the cell-by-cell
    assembly of the same read; a pass over a survivor batch is at most
    e + 1 assignments for its whole stripes; a unit stream is one
    receive where the transport receives in place."""
    opts = SCHEMES[scheme]
    k, e = opts.data_units, len(lost)
    cluster, g, data = _group(tmp_path, opts, transport, seed=sum(lost) + k)
    try:
        _lose(cluster, g, lost)
        offset, length = (0, data.size) if shape == "whole" \
            else (700, data.size - 700 - 1033)
        ref = cluster.reader(g)
        ref._put_cells = types.MethodType(_per_cell_put_cells, ref)
        want = ref.read(offset, length).copy()
        assert np.array_equal(want, data[offset:offset + length])

        r = cluster.reader(g)
        passes = []
        put_cells = r._put_cells

        def spy(out, offset, length, sb, cols, src):
            got = put_cells(out, offset, length, sb, cols, src)
            passes.append((len(sb), len(cols), *got))
            return got

        r._put_cells = spy
        before = _ops()
        got = r.read(offset, length)
        d = _delta(before)
        assert np.array_equal(got, want)

        # the survivors' pass and the decoded cells': one batch
        (sv, dec) = passes
        live = sv[1]  # data units in the read set
        assert dec[1] == e and live <= k - e
        cut = 0 if shape == "whole" else 2  # stripes the range cuts
        assert sv[0] == dec[0] == STRIPES
        assert sv[4] <= e + 1 + cut * live
        assert dec[4] <= e + cut * e
        assert d["assemble_cells"] == sv[3] + dec[3]
        assert d["assemble_strokes"] == sv[4] + dec[4]
        if shape == "whole":
            assert sv[2:] == (STRIPES * live * CELL, STRIPES * live,
                              sv[4])
            assert d["assemble_cells"] / d["assemble_strokes"] >= 4
        # the fill: every unit of the read set, its cells of the batch
        width = d["fill_cells"] // STRIPES
        assert d["fill_cells"] == STRIPES * width and width >= live
        if transport == "native":
            assert d["survivor_cells_in_place"] == d["fill_cells"]
            assert d["fill_cells"] / d["fill_strokes"] == STRIPES
        else:  # the in-process client offers the verb, and copies
            assert d["survivor_cells_in_place"] == 0
            assert d["fill_strokes"] == d["fill_cells"]
    finally:
        cluster.close()
