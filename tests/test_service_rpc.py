"""The external shuffle service's network front door (PR 20).

What is pinned here:

- the wire protocol round-trips and CRC-rejects mangled frames;
- the RPC session surface is BIT-IDENTICAL to the in-process surface
  (same records, same totals, same bytes);
- retried mutations are applied once (idempotent ``req_id`` replay);
- a chaos schedule on ``rpc.send``/``rpc.recv`` (fail/corrupt/delay)
  is survived with balanced fault books — hard injections == client
  retries + recoveries + degradations;
- an expired lease is reaped exactly like a clean ``close_session``
  (tickets returned, tenant charges released, shuffles dropped) with a
  journaled schema-v14 ``{"kind": "lease"}`` line, and the v13↔v14
  interchange is pure kind-tolerance;
- (slow) a SIGKILLed client's lease is reaped within the heartbeat
  bound, and a SIGKILLed-and-relaunched daemon completes an in-flight
  job with the finished stage adopted via ``resume_segments`` — the
  journal shows the adoption and ZERO duplicate exchange spans.
"""

import os
import pathlib
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from sparkrdma_tpu import faults
from sparkrdma_tpu.config import ShuffleConf
from sparkrdma_tpu.obs.journal import (SCHEMA_VERSION, read_entries,
                                       read_journal)
from sparkrdma_tpu.service import (RpcCallError, RpcClient,
                                   ShuffleService)
from sparkrdma_tpu.service import wire
from sparkrdma_tpu.service.rpc import lease_line

REPO = pathlib.Path(__file__).resolve().parent.parent


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _sub_env() -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")}
    env.update({"PYTHONPATH": str(REPO), "JAX_PLATFORMS": "cpu"})
    return env


def _records(conf: ShuffleConf, mesh: int, rpd: int,
             seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2**32, size=(mesh * rpd, conf.record_words),
                        dtype=np.uint32)


def _inproc_control(svc: ShuffleService, x: np.ndarray,
                    shuffle_id: int) -> tuple:
    """The same exchange through the in-process session surface."""
    import jax

    from sparkrdma_tpu.exchange.partitioners import hash_partitioner

    m = svc.open_session("control")
    try:
        mesh = m.runtime.num_partitions
        h = m.register_shuffle(shuffle_id, mesh,
                               hash_partitioner(mesh, m.conf.key_words))
        try:
            m.get_writer(h).write(m.runtime.shard_records(x)).stop(True)
            rows, totals = m.get_reader(h).read()
            return (np.asarray(jax.device_get(rows)).copy(),
                    np.asarray(jax.device_get(totals)).copy())
        finally:
            m.unregister_shuffle(shuffle_id)
    finally:
        svc.close_session(m)


# ---------------------------------------------------------------------
# wire protocol
# ---------------------------------------------------------------------

class TestWire:
    def test_frame_roundtrip(self):
        a, b = socket.socketpair()
        try:
            obj = {"op": "hello", "args": {"n": [1, 2, 3]},
                   "s": "uniçode"}
            wire.send_frame(a, obj)
            assert wire.recv_frame(b) == obj
        finally:
            a.close()
            b.close()

    def test_mangled_frame_fails_crc(self):
        a, b = socket.socketpair()
        try:
            plane = faults.FaultPlane("rpc.send:corrupt@attempt<1")
            with faults.scoped_plane(plane):
                wire.send_frame(a, {"op": "x"})
            with pytest.raises(wire.FrameError):
                wire.recv_frame(b)
            assert plane.injected_total(("corrupt",)) == 1
        finally:
            a.close()
            b.close()

    def test_oversized_length_prefix_refused(self):
        a, b = socket.socketpair()
        try:
            a.sendall(b"\xff\xff\xff\xff\x00\x00\x00\x00")
            with pytest.raises(wire.FrameError, match="exceeds cap"):
                wire.recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_peer_close_is_connection_error(self):
        a, b = socket.socketpair()
        a.close()
        try:
            with pytest.raises(ConnectionError):
                wire.recv_frame(b)
        finally:
            b.close()

    def test_new_fault_sites_registered_and_corruptible(self):
        assert "rpc.send" in faults.SITES
        assert "rpc.recv" in faults.SITES
        assert "rpc.send" in faults.CORRUPTIBLE
        assert "rpc.recv" in faults.CORRUPTIBLE
        # corrupt on an rpc site must parse (pre-PR it raised)
        faults.parse_fault_spec("rpc.recv:corrupt@0.5")


# ---------------------------------------------------------------------
# lease journal line (schema v14)
# ---------------------------------------------------------------------

class TestLeaseLine:
    def test_fields_pin_and_schema(self):
        line = lease_line("grant", "c1", tenant="blue", sessions=1,
                          age_s=1.5, ttl_s=30.0, detail="d")
        assert set(line) == wire.LEASE_FIELDS
        assert SCHEMA_VERSION == 14
        assert line["schema"] == 14

    def test_v13_v14_interchange_is_kind_tolerance(self, tmp_path):
        # a v14 journal mixing spans and lease lines: the span reader
        # (a v13 consumer's view) skips the unknown kind losslessly,
        # the entry reader surfaces it
        path = str(tmp_path / "j.jsonl")
        from sparkrdma_tpu.obs.journal import ExchangeJournal, ExchangeSpan
        j = ExchangeJournal(path)
        j.emit(ExchangeSpan(span_id=1, shuffle_id=9, transport="ici",
                            rounds=1, dispatches=1, records=8,
                            record_bytes=16, plan_s=0.0, exchange_s=0.0,
                            sort_s=0.0, per_peer_records=[8]))
        j.emit_raw(lease_line("expire", "c1", tenant="blue"))
        j.close()
        spans = read_journal(path)
        assert [s.shuffle_id for s in spans] == [9]
        kinds = [e.get("kind") for e in read_entries(path)]
        assert "lease" in kinds


# ---------------------------------------------------------------------
# in-process client/server
# ---------------------------------------------------------------------

@pytest.fixture()
def svc(tmp_path):
    conf = ShuffleConf(rpc_port=0, lease_s=30.0,
                       spill_dir=str(tmp_path / "ck"),
                       metrics_sink=str(tmp_path / "j.jsonl"))
    s = ShuffleService(conf=conf)
    assert s.rpc is not None
    yield s
    s.stop()


def _client(svc, client_id, **kw):
    kw.setdefault("retry_ms", 2.0)
    kw.setdefault("deadline_s", 20.0)
    return RpcClient(port=svc.rpc.port, client_id=client_id, **kw)


class TestRpcSurface:
    def test_disabled_by_default(self):
        assert ShuffleConf().rpc_port == -1

    def test_bit_identity_with_inprocess_surface(self, svc):
        mesh = svc.runtime.num_partitions
        x = _records(svc.conf, mesh, 16, seed=7)
        c = _client(svc, "bit")
        c.hello()
        s = c.open_session("blue")
        c.register_shuffle(s, 701, mesh)
        assert c.write(s, 701, x) == x.shape[0]
        rows, totals = c.read(s, 701)
        c.unregister_shuffle(s, 701)
        c.close()
        want_rows, want_totals = _inproc_control(svc, x, 702)
        assert (np.asarray(rows, np.uint32) == want_rows).all()
        assert (np.asarray(totals) == want_totals).all()

    def test_schema_mismatch_rejected(self, svc):
        s = socket.create_connection(("127.0.0.1", svc.rpc.port),
                                     timeout=5.0)
        try:
            wire.send_frame(s, {"op": "hello", "req_id": "r1",
                                "client": "old", "schema": 999,
                                "args": {}})
            reply = wire.recv_frame(s)
            assert reply["ok"] is False
            assert "schema-mismatch" in reply["error"]
            assert reply["retryable"] is False
        finally:
            s.close()

    def test_idempotent_replay_applies_mutation_once(self, svc):
        s = socket.create_connection(("127.0.0.1", svc.rpc.port),
                                     timeout=5.0)
        try:
            def call(op, req_id, args):
                wire.send_frame(s, {
                    "op": op, "req_id": req_id, "client": "idem",
                    "schema": wire.RPC_SCHEMA_VERSION, "args": args})
                return wire.recv_frame(s)

            assert call("hello", "h1", {})["ok"]
            r1 = call("open_session", "o1", {"tenant": "blue"})
            r2 = call("open_session", "o1", {"tenant": "blue"})
            assert r1["ok"] and r1 == r2          # replayed, not re-run
            assert svc.stats()["sessions"] == 1   # applied ONCE
            assert svc.metrics.counter("service.rpc.replays").value == 1
            # a DIFFERENT req_id is a new call
            r3 = call("open_session", "o2", {"tenant": "blue"})
            assert r3["value"]["session"] != r1["value"]["session"]
            assert svc.stats()["sessions"] == 2
        finally:
            s.close()

    def test_corrupted_frame_retried_books_balance(self, svc):
        """Satellite: a mid-stream corrupted frame is retried and the
        books balance — injections == retries + recoveries. The plane
        is thread-scoped to the client half (in the real deployment
        the chaos schedule lives in the client PROCESS; in-process both
        wire halves would otherwise fire one shared plane)."""
        faults.reset_accounting()
        mesh = svc.runtime.num_partitions
        x = _records(svc.conf, mesh, 16, seed=9)
        plane = faults.FaultPlane(
            "rpc.send:corrupt@attempt<2;rpc.recv:fail@attempt<2;"
            "rpc.send:delay=2ms@0.2", seed=3)
        c = _client(svc, "chaos")
        with faults.scoped_plane(plane):
            c.hello()
            s = c.open_session("blue")
            c.register_shuffle(s, 703, mesh)
            c.write(s, 703, x)
            rows, totals = c.read(s, 703)
        hard = plane.injected_total(("fail", "corrupt"))
        assert hard >= 4
        assert set(plane.sites_hit()) >= {"rpc.send", "rpc.recv"}
        assert hard == (c.stats["retries"] + faults.recovery_total()
                        + faults.degradation_total())
        # and the faulted run is still bit-identical
        want_rows, _ = _inproc_control(svc, x, 704)
        assert (np.asarray(rows, np.uint32) == want_rows).all()
        c.close()

    def test_client_deadline_converts_outage_to_one_error(self):
        dead = _free_port()
        c = RpcClient(port=dead, client_id="dl", retry_ms=1.0,
                      deadline_s=0.4)
        t0 = time.monotonic()
        with pytest.raises(RpcCallError, match="deadline"):
            c.hello()
        assert time.monotonic() - t0 < 5.0
        assert c.stats["retries"] >= 1

    def test_locate_and_leases_ops(self, svc):
        mesh = svc.runtime.num_partitions
        x = _records(svc.conf, mesh, 8, seed=5)
        c = _client(svc, "intro")
        c.hello()
        s = c.open_session("blue")
        c.register_shuffle(s, 705, mesh)
        c.write(s, 705, x)
        c.read(s, 705, checkpoint=True)
        # adopting the checkpoint registers disk-tier segments the
        # locate op can see (and charges them to the tenant)
        v = c.resume_read(s, 705)
        assert sorted(v["adopted"]) == ["rpc705:cols", "rpc705:totals"]
        loc = c.locate("rpc705:")
        assert set(loc) == {"rpc705:cols", "rpc705:totals"}
        assert all(t in ("hbm", "host", "disk") for t in loc.values())
        rows = c.leases()
        assert len(rows) == 1
        ls = rows[0]
        assert set(ls) == wire.LEASE_FIELDS
        assert ls.get("client") == "intro"
        assert ls.get("event") == "live"
        assert ls.get("sessions") == 1
        u = c.usage()["blue"]
        assert u["host"] + u["disk"] >= 1   # the adopted segments
        c.close()

    def test_goodbye_reaps_like_close_session(self, svc):
        c = _client(svc, "bye")
        c.hello()
        c.open_session("blue")
        c.admit("blue", 1)
        assert svc.stats()["sessions"] == 1
        assert svc.stats()["admission"]["active"] == 1
        c.close()
        assert svc.stats()["sessions"] == 0
        assert svc.stats()["admission"]["active"] == 0
        events = [e["event"] for e in read_entries(svc._sink_path)
                  if e.get("kind") == "lease"]
        assert events == ["grant", "close"]


class TestLeaseExpiry:
    def test_expired_lease_reaped_like_close_session(self, tmp_path):
        """No heartbeat: the lease lapses and the server must release
        the admission ticket, zero the tenant's charges, drop the
        session, and journal the expiry."""
        conf = ShuffleConf(rpc_port=0, lease_s=0.5,
                           spill_dir=str(tmp_path / "ck"),
                           metrics_sink=str(tmp_path / "j.jsonl"))
        svc = ShuffleService(conf=conf)
        try:
            mesh = svc.runtime.num_partitions
            x = _records(conf, mesh, 8, seed=4)
            c = _client(svc, "lapsed")
            c.hello()
            # beat through the set-up calls, so a slow (loaded) run
            # cannot outlast the lease before the checkpoint is adopted;
            # a second connection under the same client id, since one
            # client's beats wait behind its own long calls; its request
            # ids start far above c's, or the server's replay cache would
            # answer c's calls with the keeper's cached replies
            keeper = _client(svc, "lapsed")
            keeper._next_req = 1 << 20
            keeper.hello()
            keeper.start_heartbeat()
            s = c.open_session("blue")
            c.admit("blue", 1)
            c.register_shuffle(s, 706, mesh)
            c.write(s, 706, x)
            c.read(s, 706, checkpoint=True)
            # adopt the checkpoint so the tenant HOLDS disk charges the
            # reap must release
            assert c.resume_read(s, 706)["adopted"]
            assert svc.stats()["sessions"] == 1
            u = svc.usage_by_tenant()["blue"]
            assert u["host"] + u["disk"] >= 1
            # from here on no heartbeat: the lease lapses
            keeper.stop_heartbeat()
            deadline = time.monotonic() + 5.0
            while (svc.stats()["sessions"] and
                   time.monotonic() < deadline):
                time.sleep(0.05)
            assert svc.stats()["sessions"] == 0, "lease never reaped"
            assert svc.stats()["admission"]["active"] == 0
            assert svc.usage_by_tenant()["blue"] == \
                {"hbm": 0, "host": 0, "disk": 0}
            assert svc.metrics.counter(
                "service.leases_expired").value == 1
            lease_events = [e for e in read_entries(svc._sink_path)
                            if e.get("kind") == "lease"]
            assert [e["event"] for e in lease_events] == \
                ["grant", "adopt", "expire"]
            exp = lease_events[-1]
            assert set(exp) == wire.LEASE_FIELDS
            assert exp["client"] == "lapsed"
            assert exp["tenant"] == "blue"
            assert exp["sessions"] == 1
            assert exp["schema"] == 14
        finally:
            svc.stop()

    def test_heartbeat_keeps_lease_alive(self, tmp_path):
        conf = ShuffleConf(rpc_port=0, lease_s=0.6)
        svc = ShuffleService(conf=conf)
        try:
            c = _client(svc, "beater")
            c.hello()
            c.start_heartbeat()          # lease_s / 3
            c.open_session("blue")
            time.sleep(1.5)              # >> lease_s without beats
            assert svc.stats()["sessions"] == 1
            assert svc.metrics.counter(
                "service.leases_expired").value == 0
            c.close()
        finally:
            svc.stop()


class TestShuffleTopLeases:
    """The monitor's ``--rpc`` lease-table mode against a live daemon.

    ``shuffle_top.py`` is stdlib-only, so it re-implements the wire
    framing inline; these tests pin that mirror against the real
    server — a frame-format or schema drift breaks them."""

    @staticmethod
    def _load_top():
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "shuffle_top_under_test",
            REPO / "scripts" / "shuffle_top.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_lease_table_renders_live_clients(self, svc, capsys):
        top = self._load_top()
        c = _client(svc, "monitor-demo")
        try:
            c.hello()
            c.open_session("blue")
            c.open_session("blue")
            addr = f"127.0.0.1:{svc.rpc.port}"
            rows = top.fetch_lease_rows(addr)
            assert [r["client"] for r in rows] == ["monitor-demo"]
            assert set(rows[0]) == wire.LEASE_FIELDS
            assert rows[0]["event"] == "live"
            assert rows[0]["sessions"] == 2
            assert rows[0]["tenant"] == "blue"
            assert 0.0 < rows[0]["ttl_s"] <= svc.conf.lease_s

            assert top.main(["--rpc", addr, "--once"]) == 0
            out = capsys.readouterr().out
            assert f"leases @ {addr} — 1 client(s)" in out
            assert "CLIENT" in out and "TTL" in out and "LIVE" in out
            line = next(ln for ln in out.splitlines()
                        if ln.startswith("monitor-demo"))
            assert "blue" in line and "live" in line
            assert "tickets=0" in line
        finally:
            c.close()
        # the clean goodbye empties the table
        assert top.fetch_lease_rows(addr) == []
        assert top.main(["--rpc", addr, "--once"]) == 0
        assert "(no live leases)" in capsys.readouterr().out

    def test_unreachable_daemon_flags_stale(self, capsys):
        top = self._load_top()
        addr = f"127.0.0.1:{_free_port()}"
        status = {}
        assert top.fetch_lease_rows(addr, retries=0,
                                    status=status) == []
        assert status == {addr: False}
        assert top.main(["--rpc", addr, "--once"]) == 0
        out = capsys.readouterr().out
        assert "STALE" in out and addr in out
        assert "(no live leases)" in out


# ---------------------------------------------------------------------
# process-level acceptance (slow: real fork/exec + SIGKILL)
# ---------------------------------------------------------------------

def _wait_sentinel(proc, tag: str, timeout_s: float = 120.0) -> str:
    deadline = time.monotonic() + timeout_s
    lines = []
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                break
            continue
        lines.append(line)
        if tag in line:
            return line
    raise AssertionError(
        f"no {tag!r} sentinel from subprocess:\n{''.join(lines)}")


@pytest.mark.slow
class TestProcessFailures:
    def test_client_sigkill_lease_reaped_within_heartbeat_bound(
            self, tmp_path):
        """(a) of the acceptance matrix: SIGKILL the CLIENT process;
        the daemon reaps its lease within 3x the heartbeat cadence
        (== lease_s) plus the reaper tick, releasing every ticket and
        charge the worker's sentinel says it held."""
        lease_s = 1.0
        conf = ShuffleConf(rpc_port=0, lease_s=lease_s,
                           spill_dir=str(tmp_path / "ck"),
                           metrics_sink=str(tmp_path / "j.jsonl"))
        svc = ShuffleService(conf=conf)
        proc = None
        try:
            proc = subprocess.Popen(
                [sys.executable, str(REPO / "tests" / "rpc_worker.py"),
                 str(svc.rpc.port), "blue", "801", "16", "21"],
                env=_sub_env(), stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
            held = _wait_sentinel(proc, "RPCHELD")
            assert "client=victim-blue" in held
            assert svc.stats()["sessions"] == 1
            assert svc.stats()["admission"]["active"] == 1
            u = svc.usage_by_tenant()["blue"]
            assert u["host"] + u["disk"] >= 1
            proc.kill()                      # SIGKILL: no goodbye
            proc.wait(timeout=10)
            t0 = time.monotonic()
            bound = 3 * (lease_s / 3) * 3    # 3 beats + CI margin
            while (svc.stats()["sessions"]
                   and time.monotonic() - t0 < bound):
                time.sleep(0.05)
            reaped_in = time.monotonic() - t0
            assert svc.stats()["sessions"] == 0, \
                f"lease not reaped in {reaped_in:.2f}s"
            assert svc.stats()["admission"]["active"] == 0
            assert svc.usage_by_tenant()["blue"] == \
                {"hbm": 0, "host": 0, "disk": 0}
            events = [e["event"] for e in read_entries(svc._sink_path)
                      if e.get("kind") == "lease"]
            assert events == ["grant", "adopt", "expire"]
        finally:
            if proc is not None and proc.poll() is None:
                proc.kill()
            svc.stop()

    def test_daemon_sigkill_restart_completes_job_without_reexchange(
            self, tmp_path):
        """(b) of the acceptance matrix: SIGKILL the DAEMON mid-job,
        relaunch on the same port; the client's retry loop reconnects,
        stage 1 is ADOPTED from its checkpoint (journal ``adopt`` lease
        line, zero duplicate exchange spans) and the two-stage job
        finishes bit-identical to an in-process control that never saw
        a kill."""
        port = _free_port()
        spill = str(tmp_path / "ck")
        sink = str(tmp_path / "journal.jsonl")
        args = [sys.executable, str(REPO / "tests" / "rpc_daemon.py"),
                str(port), spill, sink, "30.0"]
        # rpc_daemon imports _hostmesh from the repo root


        def launch():
            p = subprocess.Popen(args, env=_sub_env(),
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True)
            _wait_sentinel(p, "RPCREADY")
            return p

        conf = ShuffleConf()     # control geometry mirror (1 CPU dev)
        daemon = launch()
        proc2 = None
        try:
            c = RpcClient(port=port, client_id="driver",
                          retry_ms=50.0, deadline_s=90.0)
            c.hello()
            s = c.open_session("blue")
            # num_parts=0 lets the daemon answer with its mesh width —
            # rpc_daemon forces the same 8-device mesh as this process
            mesh = c.register_shuffle(s, 901)["num_parts"]
            x1 = _records(conf, mesh, 32, seed=33)
            c.write(s, 901, x1)
            r1, t1 = c.read(s, 901, checkpoint=True)    # stage 1 done

            daemon.kill()                                # mid-job
            daemon.wait(timeout=10)
            proc2 = launch()                             # same port

            # the retry loop reconnects + auto-re-hellos; the session
            # itself died with the daemon, so re-open and ADOPT
            with pytest.raises(RpcCallError, match="unknown-session"):
                c.resume_read(s, 901)
            s2 = c.open_session("blue")
            v = c.resume_read(s2, 901)
            assert sorted(v["adopted"]) == \
                ["rpc901:cols", "rpc901:totals"]
            assert v["rows"] == r1 and v["totals"] == t1

            # stage 2 consumes stage 1's output
            x2 = np.asarray(v["rows"], np.uint32).T.copy()
            c.register_shuffle(s2, 902, mesh)
            c.write(s2, 902, x2)
            r2, t2 = c.read(s2, 902)
            c.close()

            # control: both stages through one in-process service that
            # never died — the job's final output must be bit-identical
            ctl = ShuffleService(conf=ShuffleConf(
                spill_dir=str(tmp_path / "ctl_ck")))
            try:
                cr1, ct1 = _inproc_control(ctl, x1, 901)
                assert (np.asarray(r1, np.uint32) == cr1).all()
                assert (np.asarray(t1) == ct1).all()
                cr2, ct2 = _inproc_control(ctl, cr1.T.copy(), 902)
            finally:
                ctl.stop()
            assert (np.asarray(r2, np.uint32) == cr2).all()
            assert (np.asarray(t2) == ct2).all()

            # ONE continuous journal across both incarnations: exactly
            # one exchange span per stage — stage 1 was adopted, never
            # re-exchanged — plus the adopt lease line
            spans = read_journal(sink)
            per_sid = {}
            for sp in spans:
                per_sid[sp.shuffle_id] = per_sid.get(
                    sp.shuffle_id, 0) + 1
            assert per_sid.get(901) == 1, per_sid
            assert per_sid.get(902) == 1, per_sid
            lease_events = [e["event"] for e in read_entries(sink)
                            if e.get("kind") == "lease"]
            assert "adopt" in lease_events
            assert lease_events.count("grant") == 2    # one per daemon
        finally:
            for p in (daemon, proc2):
                if p is not None and p.poll() is None:
                    p.kill()
                    p.wait(timeout=10)
