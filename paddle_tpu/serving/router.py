"""ServingRouter: the fleet tier over the serving plane — one address
in front of N frontends, with failover and zero-loss live migration.

PR 14 put one serving stack behind a socket (``serving/frontend.py``);
this module is the tier above it, the piece that makes "frontend" a
CATTLE role: clients connect to the ROUTER's one address, frontends
REGISTER with heartbeat leases (the ``elastic/coordinator.py``
machinery, embedded — the router speaks the FleetClient wire verbatim),
and the router

* **routes** — unary ``predict`` round-robins across live, non-degraded
  members; streaming ``generate`` uses PREFIX-AFFINITY consistent
  hashing (:class:`ConsistentRing`, keyed by the prefix cache's
  (source-fingerprint, prefix-tokens) identity) so identical
  (src, prefix) requests land on the SAME member and the
  ``prefix_hit_rate`` the KV-reuse layer earns survives scale-out;
* **respects degradation** — a brownout/shed member (scraped from its
  ``health`` endpoint, and learned instantly from a typed
  ``DegradedError`` response) stops receiving NEW admissions while
  healthy peers exist, so the shed answer usually never reaches a
  client at all;
* **migrates live sessions** — planned drain (``drain(worker_id)``)
  asks the victim for a quiesced wire snapshot
  (``ServingFrontend._snapshot``), ships the serialized pages/
  allocator/backlog to a quiesced target's ``restore``, then severs
  the victim's relays so every stream re-attaches on the target;
  failover (lease lapse, or a severed relay plus a failed probe)
  restores the victim's last BANKED snapshot (its
  ``DecodeSnapshotManager`` directory — on pods the coordinator's
  disk or GCS plays that role) on a survivor. Either way the decode
  is bit-exact: sampling keys are (seed, slot, position) and the
  victim's slots land verbatim, so the re-driven tokens are the SAME
  tokens, and the (rid, seq) splice — every solo chunk carries its
  absolute position — re-drives each client stream from exactly the
  last delivered token: no duplicates, no gaps.

The relay discipline: JSON-lines cannot multiplex, so every streaming
relay owns a dedicated upstream connection. The router trims re-driven
events against the positions it already forwarded, so a plain client
sees ONE seamless stream across a migration; a resume-capable client
(``ServingClient.generate(resume=True)``) pointed at router replicas
gets the same splice one level up. A stream that genuinely cannot be
re-driven (no banked snapshot, no survivor, an unknown rid after
restore) terminates with a typed ``StreamBrokenError`` and counts on
``paddle_tpu_router_lost_streams_total`` — the metric the CI route
stage gates at 0.

Request handles: frontends mint rids PER MEMBER (every session counts
from 0), so a bare rid names a different request on every member. The
router therefore hands clients ROUTER-SCOPED composite handles —
``"<worker_id>:<rid>"`` — on every relayed event that carries an id.
The handle self-describes the minting member (it even survives a
router restart, because members re-register under stable ids), and
``take_result``/``attach`` resolve it to exactly that member, walking
the migration chain when the member's sessions moved. A bare rid (a
client that streamed from a frontend DIRECTLY and rotated to the
router) resolves only through the client's ``origin`` address hint or
an unambiguous migration record; when no unambiguous owner exists the
router answers with a typed miss — it never probes the fleet with a
bare number, which could consume or splice ANOTHER client's
same-numbered request.

Chaos sites: ``router.route`` (member selection — an ``io`` fault
re-routes under classified retry), ``migrate.ship`` (before the
snapshot payload ships — a ``kill`` is a mid-migration router death;
the snapshot stays banked, a restarted router re-runs idempotently),
``migrate.restore`` (before the target restore RPC — an ``io`` fault
retries, never loses the stream). docs/SERVING.md "Router tier"
documents the wire grammar; docs/RESILIENCE.md carries the failure
matrix rows.
"""

import bisect
import hashlib
import json
import os
import select
import socket
import threading
import time
import uuid

from paddle_tpu.distributed.master import (
    close_json_server,
    serve_json_lines,
)
from paddle_tpu.elastic.coordinator import (
    FleetClient,
    FleetCoordinator,
    FleetEvictedError,
)
from paddle_tpu.observability import lock_witness
from paddle_tpu.observability.metrics_registry import REGISTRY as _REGISTRY
from paddle_tpu.resilience import chaos as _chaos
from paddle_tpu.resilience import retry as _retry
from paddle_tpu.resilience.checkpoint import (
    complete_serials,
    read_manifest,
    verify_checkpoint_dir,
)
from paddle_tpu.serving.client import (
    ServingClient,
    StreamBrokenError,
    error_to_wire,
)
from paddle_tpu.serving.degradation import HEALTHY
from paddle_tpu.serving.server import ServingError

__all__ = ["ServingRouter", "RouterMember", "ConsistentRing"]


_router_frontends = _REGISTRY.gauge(
    "paddle_tpu_router_frontends",
    "live registered frontends behind this router")
_migrations_total = _REGISTRY.counter(
    "paddle_tpu_router_migrations_total",
    "live-session migrations landed on a target frontend (planned "
    "drains AND failover restores)")
_failovers_total = _REGISTRY.counter(
    "paddle_tpu_router_failovers_total",
    "frontend failovers executed (lease lapse or severed relay + "
    "failed probe)")
_lost_streams_total = _REGISTRY.counter(
    "paddle_tpu_router_lost_streams_total",
    "relayed streams that could not be re-driven after a frontend "
    "loss (no banked snapshot / no survivor / unknown rid) — the CI "
    "route stage gates this at 0")


class ConsistentRing(object):
    """Consistent-hash ring with virtual nodes: the affinity router.

    ~``VNODES`` points per member keep the load spread even with few
    members, and membership change moves only the keys whose arc
    changed owner — which is exactly the property that keeps
    ``prefix_hit_rate`` alive across scale-out/scale-in: a key's
    member only changes when its member changed."""

    VNODES = 64

    def __init__(self, members=()):
        self._points = []   # sorted [(hash, member)]
        self._members = set()
        for m in members:
            self.add(m)

    @staticmethod
    def _hash(text):
        data = text.encode("utf-8") if isinstance(text, str) else text
        return int.from_bytes(
            hashlib.sha256(data).digest()[:8], "big")

    def add(self, member):
        member = str(member)
        if member in self._members:
            return
        self._members.add(member)
        for v in range(self.VNODES):
            bisect.insort(self._points,
                          (self._hash("%s#%d" % (member, v)), member))

    def remove(self, member):
        member = str(member)
        if member not in self._members:
            return
        self._members.discard(member)
        self._points = [p for p in self._points if p[1] != member]

    @property
    def members(self):
        return sorted(self._members)

    def pick(self, key, skip=()):
        """The member owning ``key``'s arc, walking clockwise past any
        in ``skip``. None when every member is skipped (or the ring is
        empty)."""
        if not self._points:
            return None
        h = self._hash(key)
        i = bisect.bisect_right(self._points, (h, "￿"))
        n = len(self._points)
        for step in range(n):
            member = self._points[(i + step) % n][1]
            if member not in skip:
                return member
        return None


def _parse_wire_rid(raw):
    """``(wid, mrid)`` from a wire id. The router's composite
    ``"wid:mrid"`` handles self-describe their minting member; a bare
    integer (a rid minted by a frontend the client talked to DIRECTLY)
    parses as ``(None, mrid)``. Raises TypeError/ValueError on junk."""
    if isinstance(raw, str) and ":" in raw:
        wid, _, tail = raw.rpartition(":")
        if wid:
            return wid, int(tail)
    return None, int(raw)


class _DownstreamGone(Exception):
    """The DOWNSTREAM client cancelled in-band or disconnected while a
    relay was waiting on its upstream."""

    def __init__(self, verdict):
        super(_DownstreamGone, self).__init__(verdict)
        self.verdict = verdict


class RouterMember(object):
    """Frontend-side membership: register the frontend with a
    :class:`ServingRouter` (meta carries the serving address and the
    snapshot directory — the failover landing data) and keep the lease
    alive on a daemon heartbeat thread. An eviction (missed leases
    across a router restart) re-registers under the SAME worker id, so
    a drained member — the router pins drained ids — can never sneak
    back into rotation by rejoining."""

    def __init__(self, frontend, router_addr, snapshot_dir=None,
                 worker_id=None, auth_token=None, heartbeat_s=None):
        self._fleet = FleetClient(router_addr, auth_token=auth_token)
        self._wid = str(worker_id or "fe-%s" % uuid.uuid4().hex[:10])
        host, port = frontend.address
        if snapshot_dir is None:
            mgr = getattr(frontend, "_snap_mgr", None)
            if mgr is not None:
                snapshot_dir = mgr.checkpoint_dir
        self._meta = {"addr": "%s:%d" % (host, int(port))}
        if snapshot_dir:
            self._meta["snapshot_dir"] = os.path.abspath(snapshot_dir)
        view = self._fleet.register(self._wid, meta=self._meta)
        lease = float(view.get("lease_s") or 2.0)
        self._hb_s = (float(heartbeat_s) if heartbeat_s is not None
                      else max(0.05, lease / 3.0))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._beat, daemon=True,
            name="paddle-tpu-router-member-%s" % self._wid)
        self._thread.start()

    @property
    def worker_id(self):
        return self._wid

    def _beat(self):
        while not self._stop.wait(self._hb_s):
            try:
                self._fleet.heartbeat(self._wid)
            except FleetEvictedError:
                try:
                    self._fleet.register(self._wid, meta=self._meta)
                except Exception:  # noqa: BLE001 - keep beating
                    pass
            except Exception:  # noqa: BLE001 - transport blip: the
                pass           # client already retried once; keep beating

    def close(self, leave=True):
        self._stop.set()
        self._thread.join(timeout=5.0)
        if leave:
            try:
                self._fleet.leave(self._wid)
            except Exception:  # noqa: BLE001 - router may be gone
                pass
        self._fleet.close()


class ServingRouter(object):
    """See module docstring.

    Parameters
    ----------
    host, port : the router's one client-facing bind address.
    lease_s : frontend heartbeat lease (the failover detection bound
        for a silently dead member; severed relays detect faster).
    member_timeout_s : socket timeout for member RPCs and relays.
    health_poll_s : cadence of the degradation scrape across members
        (0 disables the poller; typed ``DegradedError`` responses
        still mark members degraded inline).
    migration_timeout_s : bound on one migration end-to-end (waiting
        out a busy target included).
    ssl_context, auth_token : the router's FRONT DOOR — TLS and bearer
        auth on the client-facing substrate (``serve_json_lines``).
        Members authenticate with the same token (FleetClient rides
        the same wire).
    member_ssl_context, member_auth_token : credentials the router
        presents TO member frontends (default: plain wire).
    """

    def __init__(self, host="127.0.0.1", port=0, lease_s=2.0,
                 member_timeout_s=10.0, health_poll_s=0.5,
                 migration_timeout_s=60.0, ssl_context=None,
                 auth_token=None, member_ssl_context=None,
                 member_auth_token=None, snapshot_path=None):
        self._mu = lock_witness.make_rlock("serving.router.mu")
        self._member_timeout_s = float(member_timeout_s)
        self._migration_timeout_s = float(migration_timeout_s)
        self._member_ssl = member_ssl_context
        self._member_auth = member_auth_token
        self._known = {}       # wid -> meta (outlives eviction: the
        #                        failover path needs addr/snapshot_dir)
        self._health = {}      # wid -> degradation state
        self._draining = set()  # wids held out of routing (drained, or
        #                         a migration landing in progress)
        self._owners = {}      # (wid, mrid) -> wid: migration records
        #                        — a restored rid's NEW owner, keyed by
        #                        the namespace it was minted in (rids
        #                        are per-member; bare numbers collide)
        self._failovers = {}   # wid -> Event (idempotence: first caller
        #                        runs, the rest wait)
        self._clients = {}     # wid -> (ServingClient, lock) unary pool
        self._relays = {}      # wid -> set of live relay clients
        self._ring = ConsistentRing()
        self._ring_gen = -1
        self._rr = 0
        self._migration_seconds = []
        self._n_migrations = 0
        self._n_failovers = 0
        self._n_lost = 0
        self._closed = threading.Event()
        self._coord = FleetCoordinator(
            lease_s=lease_s, snapshot_path=snapshot_path,
            on_evict=self._on_evict)
        self._json_server, self.address = serve_json_lines(
            self._dispatch, host=host, port=port, pass_conn=True,
            ssl_context=ssl_context, auth_token=auth_token)
        self._poller = None
        if health_poll_s and health_poll_s > 0:
            self._poller = threading.Thread(
                target=self._poll_health, args=(float(health_poll_s),),
                daemon=True, name="paddle-tpu-router-health")
            self._poller.start()

    @property
    def port(self):
        return self.address[1]

    # -- membership ----------------------------------------------------------

    def _membership(self):
        """Current live members (wid -> meta), ring kept in sync with
        the coordinator's membership generation."""
        st = self._coord.status()
        members = {}
        for wid, m in st["members"].items():
            meta = m.get("meta") or {}
            if meta.get("addr"):
                members[wid] = meta
        with self._mu:
            self._known.update(members)
            if st["generation"] != self._ring_gen:
                self._ring = ConsistentRing(members)
                self._ring_gen = st["generation"]
        _router_frontends.set(len(members))
        return members

    def _on_evict(self, wids, generation):
        """Coordinator watcher hook: a lease lapse IS the failure
        signal — run the failover off-thread so the sweep cadence
        never waits on a migration."""
        for wid in wids:
            threading.Thread(
                target=self._failover, args=(str(wid),), daemon=True,
                name="paddle-tpu-router-failover-%s" % wid).start()

    def _poll_health(self, interval_s):
        while not self._closed.wait(interval_s):
            for wid in list(self._membership()):
                try:
                    h = self._unary(wid, method="health")
                except Exception:  # noqa: BLE001 - liveness is the
                    continue       # lease's job, not the scrape's
                states = (h.get("health") or {}).values() \
                    if h.get("ok") else ()
                worst = HEALTHY
                from paddle_tpu.serving.degradation import _LEVEL
                for s in states:
                    if _LEVEL.get(s, 0) > _LEVEL.get(worst, 0):
                        worst = s
                with self._mu:
                    self._health[wid] = worst

    # -- member clients ------------------------------------------------------

    def _addr_of(self, wid):
        meta = self._known.get(wid) or {}
        addr = meta.get("addr")
        if not addr:
            raise ServingError("member %r has no serving address" % wid)
        return addr

    def _unary(self, wid, **req):
        """One request/response RPC to a member, serialized per member
        on a pooled connection (handler threads must never interleave
        frames on one socket)."""
        with self._mu:
            ent = self._clients.get(wid)
            if ent is None:
                ent = (ServingClient(
                    self._addr_of(wid),
                    timeout_s=self._member_timeout_s,
                    ssl_context=self._member_ssl,
                    auth_token=self._member_auth),
                    lock_witness.make_lock("serving.router.unary"))
                self._clients[wid] = ent
        client, lk = ent
        with lk:
            return client._call(**req)

    def _drop_member_clients(self, wid):
        with self._mu:
            ent = self._clients.pop(wid, None)
            relays = list(self._relays.pop(wid, ()))
        if ent is not None:
            ent[0].close()
        for c in relays:
            self._sever(c)

    def _stream_client(self, wid):
        c = ServingClient(
            self._addr_of(wid), timeout_s=self._member_timeout_s,
            ssl_context=self._member_ssl, auth_token=self._member_auth)
        with self._mu:
            self._relays.setdefault(wid, set()).add(c)
        return c

    def _release_stream_client(self, wid, c):
        with self._mu:
            live = self._relays.get(wid)
            if live is not None:
                live.discard(c)
        c.close()

    @staticmethod
    def _sever(client):
        """Hard-sever a relay connection from ANOTHER thread: shutdown
        unblocks the relay's pending read (a bare close would not)."""
        sock = client._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        client.close()

    # -- routing -------------------------------------------------------------

    @staticmethod
    def _affinity_key(req):
        """The prefix-affinity routing key: the same identity the
        PrefixCache keys reuse on — source bytes + source length +
        forced prefix — so equal requests land on the member whose
        cache already holds their pages."""
        src = req.get("src")
        b64 = src.get("b64", "") if isinstance(src, dict) else repr(src)
        return "%s|%s|%r" % (b64, req.get("src_len"),
                             req.get("prefix_tokens"))

    def _routable(self, members, tried=()):
        with self._mu:
            held = set(self._draining) | set(tried)
            degraded = {w for w, s in self._health.items()
                        if s != HEALTHY}
        live = [w for w in members if w not in held]
        healthy = [w for w in live if w not in degraded]
        return healthy, live

    def _pick_stream(self, key, tried):
        """Affinity pick for one admission: healthy members first
        (degradation-aware shedding), any live member as the fallback
        so a fully-degraded fleet still answers with ITS typed error
        instead of the router's."""
        members = self._membership()
        healthy, live = self._routable(members, tried)
        with self._mu:
            ring = self._ring
        skip_h = set(members) - set(healthy)
        skip_l = set(members) - set(live)
        wid = ring.pick(key, skip=skip_h)
        if wid is None:
            wid = ring.pick(key, skip=skip_l)
        return wid

    def _mark_degraded(self, wid, state):
        with self._mu:
            self._health[wid] = state or "brownout"

    # -- request ownership ---------------------------------------------------

    @staticmethod
    def _compose_rid(wid, mrid):
        """The router-scoped handle for member ``wid``'s rid ``mrid``
        — what relayed events carry downstream in place of the bare
        (per-member, collision-prone) rid."""
        return "%s:%d" % (wid, int(mrid))

    def _resolve_owner_locked(self, wid, mrid):
        """Follow the migration chain from ``(wid, mrid)`` to the
        member currently owning that rid (``wid`` itself when it never
        migrated). Caller holds ``self._mu``."""
        key = (wid, int(mrid))
        seen = set()
        while key in self._owners and key not in seen:
            seen.add(key)
            key = (self._owners[key], key[1])
        return key[0]

    def _forget_owner_locked(self, wid, mrid):
        """Drop the migration chain for one finished/claimed rid.
        Caller holds ``self._mu``."""
        key = (wid, int(mrid))
        while key in self._owners:
            key = (self._owners.pop(key), key[1])

    def _bare_rid_owner(self, mrid, members):
        """Owner for a BARE rid (no wid on the handle, no origin
        hint) — only answered when unambiguous: a unique migration
        record for that rid number, or a fleet that has only ever
        known ONE member (a single namespace). Anything else is None:
        asking every member would pop/splice ANOTHER client's
        same-numbered request, so ambiguity degrades to a typed miss,
        never to wrong data."""
        mrid = int(mrid)
        with self._mu:
            targets = {self._resolve_owner_locked(w, m)
                       for (w, m) in self._owners if m == mrid}
            known = set(self._known)
        if len(targets) == 1:
            return next(iter(targets))
        if not targets and len(known) == 1 and known <= set(members):
            return next(iter(known))
        return None

    # -- dispatch ------------------------------------------------------------

    def _dispatch(self, req, conn):
        method = req.get("method")
        if method in ("register", "heartbeat", "leave",
                      "report_reshard"):
            return self._coord._dispatch(req)
        if method == "status":
            return self._coord._dispatch(req)
        if method == "predict":
            return self._predict(req)
        if method == "generate":
            return self._generate(req, conn)
        if method == "attach":
            return self._attach(req, conn)
        if method == "cancel":
            return {"ok": True, "event": "cancelled", "idle": True}
        if method == "take_result":
            return self._take_result(req)
        if method == "metrics":
            return {"ok": True, "text": _REGISTRY.to_prometheus()}
        if method == "health":
            self._membership()
            with self._mu:
                return {"ok": True, "health": dict(self._health)}
        if method == "stats":
            return {"ok": True, "stats": self.stats()}
        if method == "drain":
            try:
                return self.drain(req.get("worker_id"))
            except Exception as exc:  # noqa: BLE001 - typed to wire
                return error_to_wire(exc)
        return error_to_wire(
            ServingError("unknown method %r" % (method,)))

    # -- unary routing -------------------------------------------------------

    def _predict(self, req):
        members = self._membership()
        if not members:
            return error_to_wire(
                ServingError("no frontends registered"))
        healthy, live = self._routable(members)
        if not live:
            return error_to_wire(
                ServingError("no routable frontends (all draining)"))
        with self._mu:
            start = self._rr
            self._rr += 1
        # round-robin WITHIN the healthy pool; degraded members are a
        # strictly-later fallback, never rotated to the front
        i = start % len(healthy) if healthy else 0
        order = (healthy[i:] + healthy[:i]
                 + [w for w in live if w not in healthy])
        last = None
        for wid in order:
            try:
                if _chaos.ENABLED:
                    _chaos.fault("router.route")
                resp = _retry.call(
                    lambda w=wid: self._unary(w, **req),
                    origin="ServingRouter.predict")
            except Exception as exc:  # noqa: BLE001 - transport/chaos:
                last = exc             # re-route to the next member
                continue
            if (not resp.get("ok", False)
                    and resp.get("etype") == "DegradedError"):
                # the degradation answer stays ON the fleet: mark the
                # member and shed this admission to the next peer —
                # the typed error reaches a client only when every
                # member refused
                self._mark_degraded(wid, resp.get("state"))
                last = resp
                continue
            return resp
        if isinstance(last, dict):
            return last
        return error_to_wire(last if isinstance(last, Exception)
                             else ServingError("no frontend answered"))

    def _take_result(self, req):
        """Claim a banked result THROUGH the router. A composite
        ``"wid:mrid"`` handle (what this router's relayed streams
        carry) resolves to its minting member through the migration
        chain; a bare rid resolves only when unambiguous
        (:meth:`_bare_rid_owner`). The resolved member — and ONLY that
        member, failed over when unreachable — is asked: rids are
        per-member namespaces, and ``take_result`` POPS, so probing
        the fleet with a bare number could consume another client's
        result."""
        try:
            wid0, mrid = _parse_wire_rid(req.get("id"))
        except (TypeError, ValueError):
            return error_to_wire(ServingError("take_result needs an id"))
        members = self._membership()
        if wid0 is None:
            wid0 = self._bare_rid_owner(mrid, members)
            if wid0 is None:
                return {"ok": True, "tokens": None}
        deadline = time.monotonic() + self._migration_timeout_s
        failed_over = set()
        while time.monotonic() < deadline:
            with self._mu:
                owner = self._resolve_owner_locked(wid0, mrid)
            if not self._member_listed(owner):
                if owner in failed_over:
                    break  # failover landed nothing new: unknown
                failed_over.add(owner)
                self._failover(owner)
                continue  # re-resolve: the restore re-owned its rids
            try:
                resp = self._unary(owner, method="take_result",
                                   id=mrid)
            except Exception:  # noqa: BLE001 - dead owner: fail over
                if owner in failed_over:
                    break
                failed_over.add(owner)
                self._failover(owner)
                continue
            if (resp.get("ok", False)
                    and resp.get("tokens") is not None):
                with self._mu:
                    self._forget_owner_locked(wid0, mrid)
            return resp
        return {"ok": True, "tokens": None}

    # -- streaming relay -----------------------------------------------------

    def _poll_downstream(self, conn):
        """'cancel' / 'eof' / None for the CLIENT-side connection —
        what the frontend's ``_read_verdict`` reads, one tier up and still
        polled by the relay's own thread (ROADMAP D15)."""
        try:
            readable, _, _ = select.select([conn.sock], [], [], 0)
        except (OSError, ValueError):
            return "eof"
        if not readable:
            return None
        try:
            peek = conn.sock.recv(4096, socket.MSG_PEEK)
        except OSError:
            return "eof"
        if not peek:
            return "eof"
        if b"\n" not in peek:
            return None
        try:
            line = conn.rfile.readline()
        except OSError:
            return "eof"
        if not line:
            return "eof"
        try:
            msg = json.loads(line)
        except ValueError:
            return "eof"
        if msg.get("method") == "cancel":
            return "cancel"
        return None

    def _relay_recv(self, upstream, conn):
        """One upstream line. The downstream is polled for an in-band
        cancel/EOF BEFORE every blocking read — so a cancel propagates
        within one event interval even while the upstream is actively
        producing (an actively-streamed readline never times out), and
        a silent upstream still gets the poll once per read timeout. A
        read timeout is NOT a sever — a parked backlog can sit silent
        far longer than the socket timeout — it just re-polls and
        waits again; EOF/transport errors surface as ConnectionError
        (the failover trigger)."""
        while True:
            verdict = self._poll_downstream(conn)
            if verdict:
                raise _DownstreamGone(verdict)
            try:
                line = upstream._rfile.readline()
            except (socket.timeout, TimeoutError):
                continue
            except (OSError, ValueError) as exc:
                raise ConnectionError("relay upstream severed: %s"
                                      % (exc,))
            if not line:
                raise ConnectionError("member closed the relay")
            try:
                return json.loads(line)
            except ValueError:
                raise ConnectionError("torn frame from member")

    def _member_listed(self, wid):
        return wid in self._membership()

    def _attach_to(self, rid, last_wid):
        """Find the CURRENT owner of member rid ``rid`` minted in
        ``last_wid``'s namespace — following the migration chain — and
        open an attach stream on it. Runs the failover when the owner
        is gone (idempotently — concurrent relays wait on one
        migration). Returns ``(client, wid, first_event)``; raises
        :class:`StreamBrokenError` when the stream is genuinely
        lost."""
        deadline = time.monotonic() + self._migration_timeout_s
        fails = 0
        while time.monotonic() < deadline:
            with self._mu:
                owner = self._resolve_owner_locked(last_wid, rid)
            if owner is None:
                break
            if not self._member_listed(owner):
                self._failover(owner)
                with self._mu:
                    new = self._resolve_owner_locked(last_wid, rid)
                if new == owner:
                    break  # no landing took ownership: lost
                continue
            client = None
            try:
                if _chaos.ENABLED:
                    _chaos.fault("router.route")
                client = self._stream_client(owner)
                client._send_line({"method": "attach", "id": int(rid)})
                first = client._recv_line()
            except (ConnectionError, EOFError, OSError,
                    ValueError) as _exc:
                if client is not None:
                    self._release_stream_client(owner, client)
                fails += 1
                if fails >= 2:
                    # severed relay + failed probe: the member is dead
                    # even if its lease hasn't lapsed yet — fail over
                    # now instead of waiting out the lease
                    self._failover(owner)
                    fails = 0
                else:
                    time.sleep(0.05)
                continue
            if first.get("ok", False):
                return client, owner, first
            self._release_stream_client(owner, client)
            if first.get("etype") == "MigrationBusyError":
                time.sleep(0.1)
                continue
            break  # typed refuse (unknown rid): lost
        with self._mu:
            self._n_lost += 1
        _lost_streams_total.inc()
        raise StreamBrokenError(
            "stream %s lost: no surviving frontend owns it (no banked "
            "snapshot covered it, or the migration found no target)"
            % rid)

    def _generate(self, req, conn, _tried=None):
        """The streaming relay (a generator the substrate drains): open
        on the affinity-picked member, forward events while tracking
        (rid, next absolute position), and on an upstream sever
        re-attach — on the same member after a transient, on the
        failover target after a death — trimming the re-driven replay
        so the downstream sees one seamless stream. ``_tried`` threads
        the skip set through a pre-admission re-route, so a severing
        member is never re-picked."""
        fwd = {k: v for k, v in req.items() if k != "trace"}
        key = self._affinity_key(fwd)
        tried = set() if _tried is None else _tried
        upstream = None
        wid = None
        rid = None       # MEMBER rid (the upstream attach handle)
        rid_wid = None   # the member namespace ``rid`` was minted in
        crid = None      # router-scoped composite handle, downstream
        next_seq = None
        admitted_fwd = False
        delivered = False
        last_exc = None
        try:
            # -- open: route the admission ------------------------------------
            while upstream is None:
                wid = self._pick_stream(key, tried)
                if wid is None:
                    yield (last_exc if isinstance(last_exc, dict)
                           else error_to_wire(
                               last_exc or ServingError(
                                   "no routable frontends")))
                    return
                try:
                    if _chaos.ENABLED:
                        _chaos.fault("router.route")
                    upstream = self._stream_client(wid)
                    upstream._send_line(fwd)
                    first = self._relay_recv(upstream, conn)
                except (ConnectionError, EOFError, OSError,
                        ValueError) as exc:
                    if upstream is not None:
                        self._release_stream_client(wid, upstream)
                        upstream = None
                    last_exc = exc
                    tried.add(wid)
                    continue
                if not first.get("ok", False):
                    if first.get("etype") == "DegradedError":
                        # shed admissions re-route to healthy peers
                        # BEFORE the typed error reaches a client
                        self._mark_degraded(wid, first.get("state"))
                        self._release_stream_client(wid, upstream)
                        upstream = None
                        last_exc = first
                        tried.add(wid)
                        continue
                    yield first
                    return
                msg = first
                break
            # -- relay --------------------------------------------------------
            while True:
                kind = msg.get("event")
                if not msg.get("ok", False):
                    yield msg
                    return
                if kind == "queued" and msg.get("id") is not None:
                    # rids are minted per-member session (every member
                    # counts from 0), so the handle the client gets is
                    # ROUTER-SCOPED: "wid:mrid". It self-describes the
                    # minting member — take_result/attach resolve it
                    # to exactly that member (through the migration
                    # chain), never by probing the fleet with a bare
                    # number that could name another client's request.
                    rid = int(msg["id"])
                    if crid is None:
                        rid_wid = wid
                        crid = self._compose_rid(wid, rid)
                    yield dict(msg, id=crid)
                elif kind == "admitted":
                    if not admitted_fwd:
                        admitted_fwd = True
                        if msg.get("id") is not None:
                            rid = int(msg["id"])
                            if crid is None:
                                rid_wid = wid
                                crid = self._compose_rid(wid, rid)
                            msg = dict(msg, id=crid)
                        if (msg.get("beam") is None
                                and msg.get("pos") is not None):
                            next_seq = int(msg["pos"]) + 1
                        yield msg
                    # else: a re-driven backlog re-admission — the
                    # client already saw its admission, swallow
                elif (kind in ("tokens", "resumed")
                        and rid is not None
                        and msg.get("seq") is not None):
                    if kind == "resumed" and not admitted_fwd:
                        # the stream failed over before its admission
                        # event but the snapshot restored it admitted:
                        # synthesize the admission the downstream never
                        # got (resumed replays from position 1, so a
                        # one-token bos prefix lines the fill up
                        # exactly)
                        admitted_fwd = True
                        yield {"ok": True, "event": "admitted",
                               "members": 1, "slots": [],
                               "prefix": [int(msg.get("bos", 0))],
                               "pos": 0,
                               "max_length": int(
                                   msg.get("max_length", 0)),
                               "eos": int(msg.get("eos", 0)),
                               "id": crid}
                    seq = int(msg["seq"])
                    toks = [int(t) for t in msg.get("tokens") or ()]
                    if next_seq is None:
                        next_seq = seq
                    if seq > next_seq:
                        with self._mu:
                            self._n_lost += 1
                        _lost_streams_total.inc()
                        yield error_to_wire(StreamBrokenError(
                            "re-driven stream %s has a token gap "
                            "(expected position %d, got %d)"
                            % (rid, next_seq, seq)))
                        return
                    keep = toks[next_seq - seq:]
                    if keep:
                        out = {"ok": True, "event": "tokens",
                               "member": int(msg.get("member", 0)),
                               "id": crid, "seq": next_seq,
                               "tokens": keep}
                        next_seq += len(keep)
                        delivered = True
                        yield out
                    if kind == "resumed" and msg.get("finished"):
                        yield {"ok": True, "event": "end", "id": crid}
                        return
                else:
                    if kind == "tokens":
                        delivered = True
                    yield (dict(msg, id=crid)
                           if (crid is not None
                               and msg.get("id") is not None)
                           else msg)
                    if kind in ("end", "cancelled"):
                        if rid is not None:
                            with self._mu:
                                self._forget_owner_locked(rid_wid, rid)
                        return
                # advance: the ONE recv point — every sever funnels
                # through the re-attach (or, pre-admission, a full
                # re-route)
                try:
                    msg = self._relay_recv(upstream, conn)
                except ConnectionError:
                    self._release_stream_client(wid, upstream)
                    upstream = None
                    if rid is None:
                        if not delivered:
                            # nothing reached the member (or the
                            # client): re-route the WHOLE admission —
                            # safe, the member's disconnect hook
                            # reclaimed whatever was admitted
                            tried.add(wid)
                            sub = self._generate(req, conn,
                                                 _tried=tried)
                            for ev in sub:
                                yield ev
                            return
                        # delivered, but the stream carries no rid
                        # (fork groups — the frontend attaches no id
                        # to their events): there is no attach handle
                        # to re-drive from. A typed, counted loss —
                        # group streams are not resumable by design.
                        with self._mu:
                            self._n_lost += 1
                        _lost_streams_total.inc()
                        yield error_to_wire(StreamBrokenError(
                            "stream severed after delivery and "
                            "carries no request id (group streams "
                            "are not resumable)"))
                        return
                    upstream, wid, msg = self._attach_to(rid, wid)
        except _DownstreamGone as gone:
            if upstream is not None:
                # drop the upstream: the member's disconnect hook
                # cancels the generation and returns slot+pages
                self._release_stream_client(wid, upstream)
                upstream = None
            if gone.verdict == "cancel":
                if rid is not None:
                    with self._mu:
                        self._forget_owner_locked(rid_wid, rid)
                yield {"ok": True, "event": "cancelled"}
            return
        except StreamBrokenError as exc:
            yield error_to_wire(exc)
            return
        except GeneratorExit:
            raise
        finally:
            if upstream is not None:
                self._release_stream_client(wid, upstream)

    def _attach(self, req, conn):
        """Router-level attach: a resume-capable client reconnecting to
        the router (or a replica) re-finds its stream wherever the
        fleet moved it. The handle must resolve to ONE member: a
        composite ``"wid:mrid"`` id self-describes its minting member
        (and survives a router restart — members re-register under
        stable ids); a bare rid needs the client's ``origin`` hint
        (the address of the frontend it was streaming from) or an
        unambiguous record, because rids are per-member namespaces and
        probing the fleet with a bare number could splice ANOTHER
        client's same-numbered stream into this caller's. Events relay
        under the caller's own handle — the CLIENT owns the splice on
        this path — but the relay still tracks positions so a second
        failover mid-attach splices correctly."""
        handle = req.get("id")
        try:
            wid0, rid = _parse_wire_rid(handle)
        except (TypeError, ValueError):
            yield error_to_wire(ServingError("attach needs an id"))
            return
        members = self._membership()
        if wid0 is None:
            origin = req.get("origin")
            if origin:
                # the client names the frontend it was DIRECTLY
                # attached to — that member's namespace minted the rid
                with self._mu:
                    cands = [w for w, meta in self._known.items()
                             if meta.get("addr") == str(origin)]
                if len(cands) == 1:
                    wid0 = cands[0]
            if wid0 is None:
                wid0 = self._bare_rid_owner(rid, members)
        if wid0 is None:
            with self._mu:
                self._n_lost += 1
            _lost_streams_total.inc()
            yield error_to_wire(StreamBrokenError(
                "attach %r: no member owns this rid unambiguously "
                "(rids are per-member namespaces — re-attach with the "
                "router's composite handle, or send the origin "
                "frontend's address)" % (handle,)))
            return
        upstream = None
        wid = None
        next_seq = None
        try:
            upstream, wid, msg = self._attach_to(rid, wid0)
            while True:
                kind = msg.get("event")
                if not msg.get("ok", False):
                    yield msg
                    return
                if (kind in ("tokens", "resumed")
                        and msg.get("seq") is not None):
                    seq = int(msg["seq"])
                    toks = [int(t) for t in msg.get("tokens") or ()]
                    if next_seq is None:
                        # first replay goes through verbatim — under
                        # the caller's OWN handle (the client trims);
                        # later re-drives trim here
                        next_seq = seq + len(toks)
                        yield (dict(msg, id=handle)
                               if msg.get("id") is not None else msg)
                    else:
                        if seq > next_seq:
                            yield error_to_wire(StreamBrokenError(
                                "re-driven stream %s has a token gap"
                                % (handle,)))
                            return
                        keep = toks[next_seq - seq:]
                        if keep:
                            yield {"ok": True, "event": "tokens",
                                   "member": int(msg.get("member", 0)),
                                   "id": handle, "seq": next_seq,
                                   "tokens": keep}
                            next_seq += len(keep)
                    if kind == "resumed" and msg.get("finished"):
                        yield {"ok": True, "event": "end",
                               "id": handle}
                        return
                else:
                    yield (dict(msg, id=handle)
                           if msg.get("id") is not None else msg)
                    if kind in ("end", "cancelled"):
                        with self._mu:
                            self._forget_owner_locked(wid0, rid)
                        return
                try:
                    msg = self._relay_recv(upstream, conn)
                except ConnectionError:
                    self._release_stream_client(wid, upstream)
                    upstream = None
                    upstream, wid, msg = self._attach_to(rid, wid)
        except _DownstreamGone as gone:
            if upstream is not None:
                self._release_stream_client(wid, upstream)
                upstream = None
            if gone.verdict == "cancel":
                yield {"ok": True, "event": "cancelled"}
            return
        except StreamBrokenError as exc:
            yield error_to_wire(exc)
            return
        finally:
            if upstream is not None:
                self._release_stream_client(wid, upstream)

    # -- migration -----------------------------------------------------------

    def _read_banked_snapshot(self, snap_dir):
        """Newest VERIFIED banked snapshot under a dead member's
        snapshot directory (shared filesystem — on pods the
        coordinator's disk or GCS plays that role), as the restore
        wire payload. None when nothing verifiable is banked."""
        try:
            serials = complete_serials(snap_dir)
        except OSError:
            return None
        for serial in reversed(serials):
            step_dir = os.path.join(snap_dir, "checkpoint_%d" % serial)
            manifest = read_manifest(step_dir)
            if manifest is None:
                continue
            if verify_checkpoint_dir(step_dir, manifest):
                continue  # problems listed: corrupt — try older
            import base64
            files = {}
            try:
                for name in sorted(os.listdir(step_dir)):
                    with open(os.path.join(step_dir, name), "rb") as f:
                        files[name] = base64.b64encode(
                            f.read()).decode("ascii")
            except OSError:
                continue
            return {"dir": os.path.basename(step_dir), "files": files}
        return None

    def _pick_target(self, exclude):
        members = self._membership()
        healthy, live = self._routable(members, tried=exclude)
        pool = healthy or live
        if not pool:
            return None
        with self._mu:
            i = self._rr
            self._rr += 1
        return sorted(pool)[i % len(pool)]

    def _ship_and_restore(self, payload, target, victim):
        """Ship a snapshot payload to ``target`` and land it: hold new
        admissions off the target, wait out its own in-flight work
        (``MigrationBusyError`` is the target saying "still draining"
        — transient by type), record the migrated rids' new owner.
        Returns the restore response or None on timeout/refusal."""
        if _chaos.ENABLED:
            _chaos.fault("migrate.ship")
        with self._mu:
            self._draining.add(target)
        try:
            deadline = time.monotonic() + self._migration_timeout_s
            while time.monotonic() < deadline:
                try:
                    if _chaos.ENABLED:
                        _chaos.fault("migrate.restore")
                    resp = _retry.call(
                        lambda: self._unary(
                            target, method="restore", **payload),
                        origin="ServingRouter.restore")
                except (ConnectionError, EOFError, OSError) as _exc:
                    time.sleep(0.1)
                    continue
                if resp.get("ok", False):
                    rids = ([int(r) for r in resp.get("live") or ()]
                            + [int(r) for r in resp.get("pending")
                               or ()]
                            + [int(r) for r in resp.get("banked")
                               or ()])
                    with self._mu:
                        for rid in rids:
                            # keyed by the namespace the rid was
                            # minted in: later lookups chain
                            # (victim, rid) -> target -> ...
                            self._owners[(victim, rid)] = target
                        self._n_migrations += 1
                    _migrations_total.inc()
                    return resp
                if resp.get("etype") == "MigrationBusyError":
                    time.sleep(0.1)
                    continue
                import logging

                logging.getLogger("paddle_tpu.serving").error(
                    "migration %s -> %s refused: %s", victim, target,
                    resp.get("error"))
                return None
            return None
        finally:
            with self._mu:
                self._draining.discard(target)

    def _failover(self, wid, timeout=None):
        """Idempotent failover for one (presumed dead) member: the
        first caller runs it, concurrent callers block until it
        lands. Safe to call for an already-failed member (no-op)."""
        wid = str(wid)
        with self._mu:
            ev = self._failovers.get(wid)
            if ev is not None:
                runner = False
            else:
                ev = threading.Event()
                self._failovers[wid] = ev
                runner = True
        if not runner:
            ev.wait(timeout if timeout is not None
                    else self._migration_timeout_s)
            return
        try:
            self._do_failover(wid)
        finally:
            ev.set()

    def _do_failover(self, wid):
        t0 = time.monotonic()
        with self._mu:
            self._n_failovers += 1
        _failovers_total.inc()
        meta = dict(self._known.get(wid) or {})
        # the victim leaves the fleet NOW (routing stops immediately;
        # the lease watcher may have already evicted it — leave() on a
        # gone member is a no-op)
        self._coord.leave(wid)
        with self._mu:
            self._health.pop(wid, None)
        self._drop_member_clients(wid)
        self._membership()
        snap_dir = meta.get("snapshot_dir")
        payload = self._read_banked_snapshot(snap_dir) \
            if snap_dir else None
        if payload is None:
            import logging

            logging.getLogger("paddle_tpu.serving").warning(
                "failover of %s: no banked snapshot to restore — its "
                "in-flight streams are lost", wid)
            return
        target = self._pick_target(exclude={wid})
        if target is None:
            import logging

            logging.getLogger("paddle_tpu.serving").warning(
                "failover of %s: no surviving frontend to restore "
                "onto", wid)
            return
        resp = self._ship_and_restore(payload, target, victim=wid)
        if resp is not None:
            with self._mu:
                self._migration_seconds.append(
                    round(time.monotonic() - t0, 6))
        from paddle_tpu.observability import blackbox

        if blackbox.ENABLED:
            blackbox.record(
                "router_failover", victim=wid, target=target,
                restored=bool(resp),
                serial=(resp or {}).get("serial"))

    def drain(self, worker_id):
        """Planned migration: quiesced wire snapshot off the (live)
        victim, ship+restore onto a peer, then sever the victim's
        relays so every stream re-attaches on the target and splices.
        The victim id stays pinned out of routing afterwards (a
        re-registration under the same id cannot rejoin rotation)."""
        wid = str(worker_id)
        members = self._membership()
        if wid not in members:
            raise ServingError("unknown frontend %r" % wid)
        t0 = time.monotonic()
        with self._mu:
            self._draining.add(wid)
        try:
            resp = _retry.call(
                lambda: self._unary(wid, method="snapshot"),
                origin="ServingRouter.snapshot")
            if not resp.get("ok", False):
                raise ServingError("drain: snapshot of %s failed: %s"
                                   % (wid, resp.get("error")))
            payload = {"dir": resp["dir"], "files": resp["files"]}
            target = self._pick_target(exclude={wid})
            if target is None:
                raise ServingError(
                    "drain: no surviving frontend to migrate onto")
            restored = self._ship_and_restore(payload, target,
                                              victim=wid)
            if restored is None:
                raise ServingError(
                    "drain: migration to %s did not land in time"
                    % target)
        except BaseException:
            # a FAILED drain must not pin a healthy member out of
            # routing forever — the pin becomes permanent only once
            # the migration actually landed
            with self._mu:
                self._draining.discard(wid)
            raise
        # membership first, then the sever: a relay that re-attaches
        # must neither route back to the victim nor race a half-
        # recorded owner map (the restore recorded owners above)
        self._coord.leave(wid)
        # mark the failover as already-done so severed relays (and the
        # eviction hook, if the member's heartbeats also stop) skip a
        # redundant restore pass
        done = threading.Event()
        done.set()
        with self._mu:
            self._failovers.setdefault(wid, done)
        self._drop_member_clients(wid)
        dt = round(time.monotonic() - t0, 6)
        with self._mu:
            self._migration_seconds.append(dt)
        return {"ok": True, "target": target,
                "serial": restored.get("serial"),
                "migration_seconds": dt,
                "live": restored.get("live"),
                "pending": restored.get("pending"),
                "banked": restored.get("banked")}

    # -- introspection / lifecycle -------------------------------------------

    def stats(self):
        members = self._membership()
        with self._mu:
            return {
                "frontends": {
                    wid: {"addr": meta.get("addr"),
                          "health": self._health.get(wid, HEALTHY),
                          "draining": wid in self._draining}
                    for wid, meta in members.items()
                },
                "generation": self._ring_gen,
                "migrations": self._n_migrations,
                "failovers": self._n_failovers,
                "lost_streams": self._n_lost,
                "migration_seconds": list(self._migration_seconds),
                "owned_requests": len(self._owners),
            }

    def close(self):
        self._closed.set()
        if self._poller is not None:
            self._poller.join(timeout=5.0)
        srv, self._json_server = self._json_server, None
        close_json_server(srv)
        self._coord.close()
        with self._mu:
            clients = list(self._clients.values())
            self._clients.clear()
            relays = [c for s in self._relays.values() for c in s]
            self._relays.clear()
        for client, _lk in clients:
            client.close()
        for c in relays:
            self._sever(c)

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        self.close()
        return False
