"""Containerized node entrypoint (the `%runscript` of the Apptainer image).

`--role head` starts a head: publishes its endpoint via the file rendezvous
(shared FS / bucket mount), serves the task protocol over TCP, and runs the
demo workload if requested. `--role worker` polls the rendezvous, HMAC-
handshakes, then pulls tasks over IP -- the paper's phases 2-4 over real
sockets. Used by the subprocess integration test and by the rendered Slurm /
K8s / GCP artifacts.

Control plane vs data plane
---------------------------

The head's TCP socket is **metadata only** in the default `p2p` data
plane: task payloads name *where* dependencies live (plus transfer
tickets authorizing the pull), results are registered by `(ref, size,
location)` while the blob stays in the producing worker's local
``NodeStore``, and workers move blobs among themselves through per-worker
**blob servers**. Aggregate data-plane bandwidth therefore scales with
the number of worker NICs instead of being capped by the head's one
socket. The legacy `relay` mode (every payload through the head) is kept
for single-node deployments and as the benchmark baseline
(``benchmarks/dataplane_bench.py``).

Control-plane ops (one HMAC-sealed JSON envelope per connection, nonce
replay protection, head TCP port):

  op           direction       request fields -> reply
  -----------  --------------  -------------------------------------------
  join         worker -> head  worker, resources, [blob_host, blob_port]
                               -> worker (assigned id), data_plane
  poll         worker -> head  worker ->
                                 p2p:   task, payload=(fn, args, kwargs),
                                        tenant, draining, deps=[{ref,
                                        size, tenant, sources=[{node,
                                        host, port, ticket}]}]
                                 relay: task, payload=(fn, args, kwargs,
                                        dep values), tenant, draining
                                 idle:  task=None, draining
                               over TCP the head holds a poll whose reply
                               would be empty until something is queued
                               for the worker (at most 50 ms); a held
                               poll's reply carries waited=<seconds held>
                               a draining p2p worker's reply may carry
                               migrations=[{ref, size, node, host, port,
                               ticket}]: direct-push drain directives the
                               worker executes source -> destination (the
                               head PREPAREd each move and minted the
                               migrate-right ticket; no payload byte of
                               the move ever touches the head)
  result_meta  worker -> head  task, worker, size -- p2p result: the blob
                               stays in the worker's store; the head
                               records (ref, size, location) only
                               -> stored, spill (spill=True asks the
                               worker to move its copy to disk: the
                               tenant is over byte quota)
  result       worker -> head  task, worker, payload (pickled value) --
                               relay mode / backward compatibility
  error        worker -> head  task, worker, err
  leave        worker -> head  worker -- idle-exit request. Refused
                               (exit=False) while the worker still solely
                               holds hot blobs; the reply's
                               replicate=[{ref, node, host, port,
                               ticket}] assigns p2p pushes that make the
                               exit safe
  ticket       worker -> head  worker, task, object -- mid-fetch re-mint:
                               fresh ticketed sources for one dep whose
                               poll-time tickets expired while earlier
                               fat deps streamed
  tickets      worker -> head  worker, task, objects=[ids] -- the batched
                               form of `ticket`: ONE round trip re-mints
                               every dep that still needs it; the reply's
                               deps=[{ok, dep | error}] aligns 1:1 with
                               `objects`, so one expired or denied dep
                               carries its own verdict instead of
                               re-minting (or failing) the whole batch
  batch        worker -> head  worker, ops=[sub-ops] -- one wire frame and
                               one cluster-lock acquisition for a worker's
                               queued lock-bound acks (result_meta, error,
                               own-cache pushed, metric_deltas), with its
                               poll riding last; sub-ops the head must
                               serve outside the lock (poll, tickets) are
                               deferred past it. Reply replies=[...]
                               aligns 1:1 with ops; a failing sub-op
                               yields its own {ok: False} without
                               poisoning the rest of the frame
  metric_deltas worker-> head  worker, deltas={counter: +n} -- data-plane
                               counter deltas (blob serves / receives /
                               served bytes) folded into per-worker head
                               aggregates surfaced by `metrics`
  pushed       worker -> head  worker, object, node -- one replicate
                               assignment landed (or a dep cache was
                               registered); the directory adds the copy
                               (third-party claims are probed first)
  migrated     worker -> head  worker (destination), object -- the
                               result_meta of the migrate protocol: the
                               destination confirms one direct drain push
                               landed in its store; the head COMMITs the
                               owner handoff only now. A late ack whose
                               move was already aborted (or whose source
                               died) is probed and, if real, registered
                               as a recovered replica
  migrate_failed worker->head  worker (source), object, retryable, err --
                               the push could not land. Retryable
                               transport faults degrade to the old
                               head-relay copy (never to lineage while
                               the head is healthy); anything else
                               ABORTs + re-plans toward a fresh
                               destination/ticket
  drain        operator->head  worker, [deadline_s] -- eviction notice
  drain_status worker -> head  worker -> complete
  stats        any -> head     -> scheduler stats + tenant shares
  metrics      adapter -> head -> autoscaling signals incl. per-tenant
                               syndeo_tenant_dominant_share and
                               syndeo_tenant_quota_fraction, plus the
                               serving-plane gauges (syndeo_serve_requests,
                               syndeo_serve_shed, syndeo_serve_p99_ms,
                               syndeo_replica_count)

Service-actor lifecycle (the serving plane): workers host long-running
replica actors instead of one-shot functions. Lifecycle directives ride
the poll reply's `actor_ops` list (head -> worker, exactly like
`migrations`); worker-side acks and results ride the existing `batch`
frame. Resources are held by the scheduler for the actor's lifetime;
actor-hosting workers refuse the idle-exit `leave` handshake and a
drain of their node completes only after every replica exits.

  op           direction       request fields -> reply
  -----------  --------------  -------------------------------------------
  actor_create client -> head  factory, [actor, resources, tenant,
                               placement_group, bundle_index, kwargs] --
                               place a replica actor; the head queues an
                               actor_create directive for the hosting
                               worker's next poll
                               -> actor, worker, cap (actor-scoped
                               capability authorizing call/exit)
  actor_call   client -> head  actor, cap, [payload, call] -- verified
                               against the actor-scoped capability, then
                               queued as an actor_call directive
                               -> call (id to fetch the result with)
  actor_result worker -> head  worker, actor, call, value|error -- a
                               finished call, riding the batch frame
               client -> head  call (no worker field) -- fetch one
                               result; over TCP held at the head until
                               it is in (at most 1 s) -> done, value|error
  actor_exit   client -> head  actor, cap -- graceful exit request,
                               queued as a directive; the replica
                               finishes in-flight work first
               worker -> head  worker, actor -- exit ack (batch frame);
                               only now does the scheduler release the
                               actor's lifetime resource hold

Blob-server wire format (worker data plane, one request per connection):
every frame is an 8-byte big-endian length followed by the payload in
64 KiB chunks (`object_store.send_frame`/`recv_frame`). Request = one
sealed-JSON frame {op: get|put|del|has, object, requester, ticket};
"put" is followed by one raw blob frame whose sha256 the sealed header
authenticates. Reply = one sealed-JSON frame {ok, size, sha256 | error};
a successful "get" is followed by the raw blob frame. Tickets
(`security.TransferTicket`) are verified under the cluster token before
any bytes move: the MAC binds (object, source, requesting worker,
tenant, right, expiry), so a ticket cannot be relabeled, replayed by
another worker, or used after its fetch window.
"""
from __future__ import annotations

import argparse
import base64
import json
import pickle
import shutil
import socket
import socketserver
import tempfile
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.core.cluster import SyndeoCluster
from repro.core.metrics import (SPANS, Histogram, MetricsHub,
                                build_cluster_metrics, render_dashboards,
                                render_prometheus)
from repro.core.object_store import (NodeStore, ObjectRef, RemoteNodeStore,
                                     TCPTransport, recv_frame, send_frame)
from repro.core.rendezvous import Endpoint, FileRendezvous
from repro.core.scheduler import WorkerInfo
from repro.core.security import (Capability, NonceCache, SecurityError,
                                 TransferTicket, open_sealed, seal)
from repro.core.task_graph import TaskState


def _enc(obj: Any) -> str:
    return base64.b64encode(pickle.dumps(obj)).decode()


def _dec(blob: str) -> Any:
    return pickle.loads(base64.b64decode(blob))


def _request(host: str, port: int, token: str, msg: Dict[str, Any],
             timeout: float = 10.0,
             nonce_cache: Optional[NonceCache] = None) -> Dict[str, Any]:
    with socket.create_connection((host, port), timeout=timeout) as s:
        s.sendall((json.dumps(seal(token, msg)) + "\n").encode())
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = s.recv(1 << 20)
            if not chunk:
                break
            buf += chunk
    return open_sealed(token, json.loads(buf.decode()),
                       nonce_cache=nonce_cache)


def push_with_retry(transport, node_id: str, ref: ObjectRef, blob: bytes,
                    ticket: Optional[TransferTicket],
                    retries: int = 1) -> Tuple[Optional[Exception], bool]:
    """One direct blob push with bounded retry. Transient TCP faults
    (refused connect, reset, timeout -- OSError family) retry `retries`
    times; protocol refusals (SecurityError: bad/expired ticket; KeyError:
    server-side refusal) never do, because retrying cannot fix them.
    Returns (error, retryable): (None, False) on success; a truthy
    retryable tells the caller to degrade to the head-relay fallback
    rather than give the move up to lineage reconstruction."""
    last: Optional[Exception] = None
    for _ in range(retries + 1):
        try:
            transport.push(node_id, ref, blob, ticket)
            return None, False
        except (SecurityError, KeyError) as e:
            return e, False
        except OSError as e:
            last = e
        except Exception as e:  # noqa: BLE001 -- malformed reply etc.
            return e, False
    return last, True


def push_batch_with_retry(transport, node_id: str,
                          items: List[Tuple[ObjectRef, bytes,
                                            Optional[TransferTicket]]],
                          retries: int = 1
                          ) -> Tuple[Optional[List[Dict[str, Any]]],
                                     Optional[Exception], bool]:
    """One multi-blob push (see TCPTransport.push_batch) with the same
    bounded-retry policy as push_with_retry. Returns (verdicts, error,
    retryable): on success the per-blob verdicts aligned 1:1 with
    `items` (individual blobs may still carry ok=False -- e.g. one
    expired ticket -- without failing the frame); on a whole-frame
    failure verdicts is None and (error, retryable) classify it exactly
    like the single-push path. Retrying a frame whose first attempt
    landed is safe: the receiving store's import is idempotent."""
    last: Optional[Exception] = None
    for _ in range(retries + 1):
        try:
            return transport.push_batch(node_id, items), None, False
        except (SecurityError, KeyError) as e:
            return None, e, False
        except OSError as e:
            last = e
        except Exception as e:  # noqa: BLE001 -- malformed reply etc.
            return None, e, False
    return None, last, True


class BlobServer:
    """Per-node data-plane server: serves one NodeStore's blobs to peers.

    Every request is ticket-checked under the cluster token (see the
    module docstring's wire format). `tenant_of(object_id)` supplies the
    object's tenant when this node knows it (its own results, cached
    deps); for unknown objects the ticket's own tenant binding -- already
    cross-checked at mint time by the head -- is authoritative."""

    #: pre-auth request headers are tiny sealed JSON -- cap them well below
    #: the blob-frame limit so an unauthenticated peer cannot buffer GiBs
    MAX_HEADER_BYTES = 64 * 1024
    SOCKET_TIMEOUT_S = 30.0

    def __init__(self, store: NodeStore, token: str,
                 host: str = "127.0.0.1", port: int = 0,
                 tenant_of: Optional[Callable[[str], Optional[str]]] = None,
                 on_delete: Optional[Callable[[str], None]] = None,
                 on_migrate: Optional[Callable[[str, str], None]] = None,
                 on_migrate_many: Optional[
                     Callable[[List[Tuple[str, str]]], None]] = None):
        self.store = store
        self.token = token
        self.tenant_of = tenant_of or (lambda oid: None)
        self.on_delete = on_delete
        # called as on_migrate(object_id, tenant_id) after a put arriving
        # under a "migrate"-right ticket lands: the destination's hook to
        # send the head the metadata ack that COMMITs the move
        self.on_migrate = on_migrate
        # batched twin: on_migrate_many([(object_id, tenant_id), ...])
        # fires ONCE for all migrate-right blobs of a put_batch frame so
        # the destination can ack N moves in one control round trip;
        # when unset, on_migrate fires per blob as before
        self.on_migrate_many = on_migrate_many
        self._nonces = NonceCache()
        self.stats = {"serves": 0, "served_bytes": 0,
                      "receives": 0, "rejects": 0,
                      "batched_moves": 0}
        blob_srv = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                blob_srv._handle(self.request)

        self.server = socketserver.ThreadingTCPServer((host, port), Handler,
                                                      bind_and_activate=True)
        self.server.daemon_threads = True
        self.host = host
        self.port = self.server.server_address[1]
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        daemon=True,
                                        name=f"blob-{store.node_id}")
        self._thread.start()

    @property
    def endpoint(self) -> Tuple[str, int]:
        return (self.host, self.port)

    def shutdown(self):
        self.server.shutdown()
        # shutdown() only stops serve_forever; the listening socket fd
        # stays open until server_close()
        self.server.server_close()

    # -- one request ----------------------------------------------------------

    def _handle(self, sock: socket.socket):
        blob_out: Optional[bytes] = None
        try:
            sock.settimeout(self.SOCKET_TIMEOUT_S)   # a stalled peer cannot
            # pin this handler thread forever
            header = open_sealed(self.token,
                                 json.loads(recv_frame(
                                     sock, self.MAX_HEADER_BYTES).decode()),
                                 nonce_cache=self._nonces)
            blob_in = None
            put_ticket = None
            batch_tickets = None
            if header.get("op") == "put":
                # ticket verified BEFORE the blob frame is read, and the
                # read is capped at the header's declared size -- a peer
                # without a valid put ticket cannot make us buffer bytes
                try:
                    put_ticket = self._verify(header, "put")
                except Exception:
                    # the client streams the blob right behind the header;
                    # closing with the frame unread RSTs the connection,
                    # which can break the client's in-flight send AND
                    # destroy the queued error reply -- the refusal then
                    # looks like a retryable transport fault instead of a
                    # SecurityError. Drain (read and discard, bounded by
                    # the declared size) so the refusal travels back clean.
                    self._drain_frame(
                        sock, int(header.get("size", 0)) + 1024)
                    raise
                blob_in = recv_frame(
                    sock, max_bytes=int(header.get("size", 0)) + 1024)
            elif header.get("op") == "put_batch":
                # same discipline as put, per blob: EVERY declared blob's
                # ticket is verified before the multi-blob frame is read;
                # a frame where no declaration verified is drained and
                # refused wholesale -- an unauthorized peer still cannot
                # make us buffer payload bytes
                batch_tickets, total = self._verify_batch(header)
                if any(t is not None for t, _err in batch_tickets):
                    blob_in = recv_frame(sock, max_bytes=total + 1024)
                else:
                    self._drain_frame(sock, total + 1024)
            reply, blob_out = self._dispatch(header, blob_in, put_ticket,
                                             batch_tickets)
        except Exception as e:  # noqa: BLE001 -- reply, never crash the server
            self.stats["rejects"] += 1
            reply = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        try:
            send_frame(sock, json.dumps(seal(self.token, reply)).encode())
            if blob_out is not None:
                send_frame(sock, blob_out)
        except OSError:
            pass                       # peer went away mid-reply

    @staticmethod
    def _drain_frame(sock: socket.socket, max_bytes: int):
        """Best-effort read-and-discard of one frame (refused put)."""
        try:
            recv_frame(sock, max_bytes=max_bytes)
        except (OSError, ValueError):
            pass                       # peer gone or oversized: just close

    def _verify(self, header: Dict[str, Any], right: str) -> TransferTicket:
        return self._verify_entry(header, str(header.get("requester", "")),
                                  right)

    def _verify_entry(self, entry: Dict[str, Any], requester: str,
                      right: str) -> TransferTicket:
        """Ticket check for one blob declaration -- a top-level header or
        one element of a put_batch frame's "blobs" list."""
        oid = entry.get("object", "")
        ticket_wire = entry.get("ticket")
        if not ticket_wire:
            raise SecurityError(f"blob {right} without transfer ticket")
        ticket = TransferTicket.from_wire(ticket_wire)
        if right == "put" and ticket.right == "migrate":
            # a drain-move push arrives as a put under the "migrate"
            # right; the right is inside the MAC, so verifying against
            # the declared right never widens what the head granted
            right = "migrate"
        tenant = self.tenant_of(oid)
        ticket.verify(self.token, oid, self.store.node_id,
                      requester, right,
                      object_tenant=tenant if tenant is not None
                      else ticket.tenant_id)
        return ticket

    def _verify_batch(self, header: Dict[str, Any]
                      ) -> Tuple[List[Tuple[Optional[TransferTicket],
                                            Optional[str]]], int]:
        """Pre-payload ticket pass over a put_batch frame's declarations:
        per-blob (ticket, None) or (None, error) verdict seeds, plus the
        total declared payload size bounding the frame read."""
        blobs = header.get("blobs")
        if not isinstance(blobs, list) or not blobs:
            raise ValueError("put_batch without blob declarations")
        requester = str(header.get("requester", ""))
        state: List[Tuple[Optional[TransferTicket], Optional[str]]] = []
        total = 0
        for b in blobs:
            total += max(0, int(b.get("size", 0)))
            try:
                state.append((self._verify_entry(b, requester, "put"), None))
            except Exception as e:  # noqa: BLE001 -- per-blob verdict
                state.append((None, f"{type(e).__name__}: {e}"))
        return state, total

    def _dispatch(self, header: Dict[str, Any],
                  blob_in: Optional[bytes],
                  put_ticket: Optional[TransferTicket] = None,
                  batch_tickets: Optional[
                      List[Tuple[Optional[TransferTicket],
                                 Optional[str]]]] = None
                  ) -> Tuple[Dict[str, Any], Optional[bytes]]:
        import hashlib
        op = header.get("op")
        if op == "put_batch":
            # tickets already verified by _handle BEFORE the multi-blob
            # frame was read (same discipline as put); slice the payload
            # by the declared sizes and give every blob its own verdict
            return self._put_batch(header, blob_in, batch_tickets), None
        oid = str(header.get("object", ""))
        ref = ObjectRef(oid)
        if op == "get":
            self._verify(header, "get")
            blob = self.store.export_blob(ref)
            self.stats["serves"] += 1
            self.stats["served_bytes"] += len(blob)
            return ({"ok": True, "size": len(blob),
                     "sha256": hashlib.sha256(blob).hexdigest()}, blob)
        if op == "put":
            # already verified by _handle BEFORE the blob frame was read
            # (the authoritative check); no second MAC computation here
            if blob_in is None:
                raise ValueError("put without blob frame")
            if (len(blob_in) != int(header.get("size", -1))
                    or hashlib.sha256(blob_in).hexdigest()
                    != header.get("sha256")):
                raise SecurityError(f"blob integrity check failed for {oid}")
            fresh = self.store.import_blob(ref, blob_in)
            if fresh:
                # attempt-idempotent accounting: a retried push whose
                # first attempt actually landed (the reply was lost, not
                # the blob) must not count the same bytes twice
                self.stats["receives"] += 1
            if (put_ticket is not None and put_ticket.right == "migrate"
                    and self.on_migrate is not None):
                # destination-side metadata ack: the head COMMITs the
                # directory's owner handoff only on this signal
                self.on_migrate(oid, put_ticket.tenant_id)
            return ({"ok": True}, None)
        if op == "has":
            # existence is placement metadata: ticketed like a read, so a
            # tenant cannot probe where another tenant's results live
            self._verify(header, "get")
            return ({"ok": True, "has": self.store.has(ref)}, None)
        if op == "del":
            self._verify(header, "del")
            self.store.delete(ref)
            if self.on_delete is not None:
                self.on_delete(oid)    # e.g. prune the owner's tenant map
            return ({"ok": True}, None)
        raise ValueError(f"unknown blob op {op!r}")

    def _put_batch(self, header: Dict[str, Any],
                   blob_in: Optional[bytes],
                   batch_tickets: List[Tuple[Optional[TransferTicket],
                                             Optional[str]]]
                   ) -> Dict[str, Any]:
        """Land a multi-blob push frame: the payload is the declared
        blobs concatenated in header order, each integrity-checked
        against its own (size, sha256) and imported independently --
        verdicts align 1:1 with the declarations, so one refused ticket
        or corrupt slice never poisons its neighbors. Migrate-right
        blobs are acked through ONE on_migrate_many call (the batched
        `migrated` control frame) instead of one round trip each."""
        import hashlib
        blobs = header.get("blobs") or []
        results: List[Dict[str, Any]] = []
        landed_moves: List[Tuple[str, str]] = []
        off = 0
        for decl, (ticket, err) in zip(blobs, batch_tickets):
            oid = str(decl.get("object", ""))
            size = max(0, int(decl.get("size", 0)))
            chunk = (blob_in[off:off + size]
                     if blob_in is not None else b"")
            off += size
            if err is not None:
                results.append({"ok": False, "object": oid, "error": err})
                continue
            if (len(chunk) != size or hashlib.sha256(chunk).hexdigest()
                    != decl.get("sha256")):
                results.append({"ok": False, "object": oid,
                                "error": "SecurityError: blob integrity "
                                         f"check failed for {oid}"})
                continue
            fresh = self.store.import_blob(ObjectRef(oid), chunk)
            if fresh:
                self.stats["receives"] += 1
                self.stats["batched_moves"] += 1
            if ticket.right == "migrate":
                landed_moves.append((oid, ticket.tenant_id))
            results.append({"ok": True, "object": oid})
        if landed_moves:
            if self.on_migrate_many is not None:
                self.on_migrate_many(landed_moves)
            elif self.on_migrate is not None:
                for oid, tenant in landed_moves:
                    self.on_migrate(oid, tenant)
        return {"ok": True, "results": results}


class HeadServer:
    """TCP face of a SyndeoCluster (pull-based workers).

    `data_plane="p2p"` (default): workers that advertise a blob endpoint
    at join get metadata-only polls (dep locations + transfer tickets)
    and register their results by size; the head's directory gains a
    RemoteNodeStore proxy per worker so get/migrate/release keep working
    over remote primaries, and the head runs its own BlobServer so
    client-put artifacts are fetchable without relaying through the
    control socket. Workers that join without a blob endpoint -- and
    every worker when `data_plane="relay"` -- take the legacy path where
    the head resolves deps and stores results itself.

    `head_payload_bytes` counts data-plane payload bytes that transited
    the head's control socket (dep values + result pickles in relay
    mode); the CI dataplane smoke asserts it stays 0 under p2p.

    Long polls: over the wire, a poll whose reply would carry nothing is
    held until something is queued for its worker, at most
    `POLL_HOLD_S`; a client's `actor_result` is held until the result is
    stored, at most `RESULT_WAIT_CAP_S`. Both wait outside the cluster
    lock."""

    # the longest an empty poll is held: the pause a worker used to take
    # between polls, so an idle worker still polls ~20 times a second
    POLL_HOLD_S = 0.05
    # the longest a client's actor_result is held, well under `_request`'s
    # socket timeout
    RESULT_WAIT_CAP_S = 1.0

    def __init__(self, cluster: SyndeoCluster, host: str = "127.0.0.1",
                 port: int = 0, data_plane: Optional[str] = None,
                 ticket_ttl_s: float = 30.0):
        self.cluster = cluster
        self.data_plane = data_plane or getattr(cluster, "data_plane", "p2p")
        data_plane = self.data_plane
        self.ticket_ttl_s = ticket_ttl_s
        # migrate tickets live longer than fetch tickets: the directive
        # waits for the source's next poll before any byte moves
        self.migrate_ttl_s = max(ticket_ttl_s, 60.0)
        self._outbox: Dict[str, list] = {}
        self._blob_eps: Dict[str, Tuple[str, int]] = {}
        # per-worker data-plane counter aggregates fed by the piggybacked
        # metric_deltas sub-op (mutated under the cluster lock)
        self._worker_metrics: Dict[str, Dict[str, int]] = {}
        # PREPAREd drain-move directives awaiting each source worker's
        # next poll ({ref, size, node, host, port, ticket} dicts)
        self._pending_migrations: Dict[str, List[Dict[str, Any]]] = {}
        # serving plane: actor lifecycle directives awaiting each hosting
        # worker's next poll, completed call results awaiting client
        # pickup, actor ids already asked to exit (a draining host asks
        # each replica exactly once), and router-fed serving gauges
        # (requests / shed / p99_ms) surfaced by the `metrics` op
        self._actor_outbox: Dict[str, List[Dict[str, Any]]] = {}
        self._actor_results: Dict[str, Dict[str, Any]] = {}
        self._actor_exits_asked: set = set()
        self._actor_create_errors: Dict[str, str] = {}
        # call id -> perf_counter when its current wait began: queued in
        # the actor outbox (`head.outbox` span), then its result held for
        # the client (`head.held` span)
        self._call_wait: Dict[str, float] = {}
        # held requests wait on `_arrivals`; `_arrived` counts, per
        # worker, what was queued for it and changes only under the
        # condition, so a poll that reads its count before looking at its
        # queues cannot miss an arrival that lands in between.
        self._arrivals = threading.Condition()
        self._arrived: Dict[str, int] = {}
        self._closing = False
        self.serve_stats: Dict[str, float] = {}
        # observability hub: shares the scheduler's registry (sojourn
        # histograms land there) and folds worker-pushed histogram
        # deltas into it; every `metrics` snapshot is recorded into the
        # hub's ring-buffer time series for dashboard history
        self.metrics_hub = MetricsHub(registry=cluster.scheduler.metrics)
        # instrument cache for the delta fold: the registry lookup
        # (lock + family/key build) costs ~2x the fold itself, and the
        # hot path folds the same few histogram names every poll
        self._hist_cache: Dict[str, Any] = {}
        self.head_payload_bytes = 0
        # bounded seen-nonce set: a captured worker envelope cannot be
        # replayed inside the freshness window (it would need a fresh nonce,
        # and the nonce is under the MAC)
        self._nonces = NonceCache()
        self._blob_srv: Optional[BlobServer] = None
        if data_plane == "p2p":
            self._blob_srv = BlobServer(cluster._head_node, cluster.token,
                                        host=host,
                                        on_migrate=self._head_migrate_ack)
            # drain migrations are peer-to-peer: the head PREPAREs each
            # move and hands the source worker a push directive; only the
            # relay fallback (below) still copies through this process
            cluster.scheduler.migrate_fn = self._migrate_directive
            # a dead source's queued directives can never be delivered:
            # drop them with the worker (same wrap style as attach())
            orig_failed = cluster.scheduler.on_worker_failed

            def on_failed(worker_id, reason="failure"):
                self._pending_migrations.pop(worker_id, None)
                orig_failed(worker_id, reason)

            cluster.scheduler.on_worker_failed = on_failed
        # a drain that begins changes what the worker's next poll says
        orig_drain = cluster.scheduler.begin_drain

        def begin_drain(worker_id, deadline_s=None):
            ok = orig_drain(worker_id, deadline_s)
            if ok:
                self._wake(worker_id)
            return ok

        cluster.scheduler.begin_drain = begin_drain
        head = self

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                line = self.rfile.readline()
                try:
                    msg = open_sealed(cluster.token,
                                      json.loads(line.decode()),
                                      nonce_cache=head._nonces)
                    reply = head.dispatch(msg, hold=True)
                except Exception as e:  # noqa: BLE001
                    reply = {"ok": False, "error": str(e)}
                self.wfile.write(
                    (json.dumps(seal(cluster.token, reply)) + "\n").encode())

        self.server = socketserver.ThreadingTCPServer((host, port), Handler,
                                                      bind_and_activate=True)
        self.server.daemon_threads = True
        self.port = self.server.server_address[1]
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        daemon=True)
        self._thread.start()
        # re-publish the rendezvous with the real TCP port
        cluster.rendezvous.publish(Endpoint(host, self.port,
                                            cluster.cluster_id, cluster.token))

    # head-side handling ------------------------------------------------------

    def _migrate_directive(self, worker_id: str, ref: ObjectRef, dst: str):
        """Scheduler migrate hook for the p2p head: PREPARE the move
        (directory in-flight state + migrate-right ticket) and queue a
        push directive for the source worker's next poll. The blob then
        moves *directly* source -> destination; the destination's
        `migrated` ack COMMITs; a move that never acks is aborted and
        re-planned by the scheduler's timeout sweep. Sources without a
        blob endpoint (relay-joined workers, whose stores live in this
        process) keep the old head-side copy path."""
        c = self.cluster
        dst_ep = self._source_endpoints(dst)
        if worker_id not in self._blob_eps or dst_ep is None:
            self._migrate_relay(worker_id, ref, dst)
            return
        try:
            if not c.store.begin_move(ref, worker_id, dst):
                c.scheduler.note_migration_failed(worker_id, ref)
                return
            ticket = c.store.migrate_ticket(ref, worker_id, dst,
                                            ttl_s=self.migrate_ttl_s)
        except SecurityError:
            c.scheduler.note_migration_denied(worker_id, ref)
            return
        self._pending_migrations.setdefault(worker_id, []).append({
            "ref": ref.id, "size": ref.size, "node": dst,
            "host": dst_ep[0], "port": dst_ep[1],
            "ticket": ticket.to_wire(),
            # remaining drain budget (None = no deadline): preemption
            # notices race the notice window, so the source worker
            # batches and orders its pushes deadline-soonest-first
            "deadline_s": c.scheduler.drain_deadline_s(worker_id)})
        self._wake(worker_id)

    def _migrate_relay(self, worker_id: str, ref: ObjectRef, dst: str):
        """Head-relayed move on a background thread (the blocking
        export/import RPCs run lock-free): the pre-p2p path, kept for
        relay-joined workers and as the transient-transport *fallback* --
        strictly better than lineage reconstruction while the head is
        healthy. Bytes relayed for remote endpoints are counted against
        the head's NIC (head_relayed_bytes)."""
        c = self.cluster

        def run():
            try:
                moved = c.store.migrate(ref, worker_id, dst)
            except SecurityError:
                with c._lock:
                    c.scheduler.note_migration_denied(worker_id, ref)
                return
            except Exception:  # noqa: BLE001 -- e.g. peer unreachable
                moved = False
            if moved and (worker_id in self._blob_eps
                          or dst in self._blob_eps):
                c.store.stats["head_relayed_bytes"] += \
                    c.store.size_of(ref) or ref.size
            with c._lock:
                if moved:
                    c.scheduler.note_migrated(worker_id, ref)
                else:
                    c.scheduler.note_migration_failed(worker_id, ref)

        threading.Thread(target=run, daemon=True,
                         name=f"migrate-{ref.id[:8]}").start()

    def _head_migrate_ack(self, oid: str, tenant: str):
        """on_migrate hook of the head's own blob server: a drain push
        whose destination is the head store commits here directly (there
        is no remote worker to send the `migrated` op)."""
        c = self.cluster
        mv = c.store.move_in_flight(oid)
        if mv is None or mv[1] != "head":
            return
        src, dst = mv
        if c.store.commit_move(oid, src, dst):
            with c._lock:
                c.scheduler.note_migrated(src, ObjectRef(oid))

    def _source_endpoints(self, node_id: str) -> Optional[Tuple[str, int]]:
        if node_id in self._blob_eps:
            return self._blob_eps[node_id]
        if node_id == "head" and self._blob_srv is not None:
            return self._blob_srv.endpoint
        return None

    def _dep_meta(self, d: ObjectRef, wid: str,
                  tenant: str) -> Dict[str, Any]:
        """Metadata-only descriptor for ONE dependency: its size, tenant,
        and up to three ticketed sources ordered worker-peers first, idle
        links first. Cross-tenant deps are refused here, at mint time --
        the polling worker never learns where the bytes are. Also serves
        the `ticket` op, which re-mints mid-fetch when a long chain
        outlives the tickets batched at poll time."""
        c = self.cluster
        own = c.store.tenant_of(d.id)
        if own is not None and own != tenant:
            raise SecurityError(
                f"cross-tenant dep denied: task of tenant {tenant!r} "
                f"depends on an object of tenant {own!r}")
        locs = c.store.rank_sources(d, wid)
        sources = []
        for n in locs:
            ep = self._source_endpoints(n)
            if ep is None:
                continue
            ticket = TransferTicket.grant(
                c.token, d.id, n, wid, tenant, "get",
                ttl_s=self.ticket_ttl_s)
            sources.append({"node": n, "host": ep[0], "port": ep[1],
                            "ticket": ticket.to_wire()})
            if len(sources) >= 3:
                break
        if not sources and locs:
            # every copy sits in an endpoint-less head-process store
            # (a relay worker's node store, e.g. after a migration):
            # stage a head copy and serve it from the head blob server
            try:
                c.store.fetch("head", d)
                ep = self._source_endpoints("head")
                if ep is not None:
                    ticket = TransferTicket.grant(
                        c.token, d.id, "head", wid, tenant, "get",
                        ttl_s=self.ticket_ttl_s)
                    sources.append({"node": "head", "host": ep[0],
                                    "port": ep[1],
                                    "ticket": ticket.to_wire()})
            except KeyError:
                pass                   # no live copy: the worker reports it
        return {"ref": d.id, "size": c.store.size_of(d),
                "tenant": own or tenant, "sources": sources}

    def _deps_meta(self, task, wid: str, tenant: str) -> List[Dict[str, Any]]:
        return [self._dep_meta(d, wid, tenant) for d in task.deps]

    def _fail_task(self, tid: str, wid: str, err: str):
        c = self.cluster
        with c._lock:
            c.scheduler.on_task_failed(tid, err, worker_id=wid)
        ev = c._futures.get(tid)
        if ev:
            ev.set()

    # lock-bound sub-handlers -------------------------------------------------
    # These serve both their top-level op and the `batch` frame's inlined
    # path: everything in them is metadata work (directory + scheduler
    # bookkeeping, no data-plane I/O), so a batch may run them all under
    # ONE cluster-lock acquisition (the lock is reentrant).

    def _handle_result_meta(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """p2p result registration: the blob already lives in the worker's
        local store; the head records (ref, size, location) -- same tenant
        + quota admission as a relayed put, zero payload bytes here."""
        c = self.cluster
        tid, wid = msg["task"], msg["worker"]
        size = int(msg["size"])
        with c._lock:
            task = c.scheduler.graph.tasks.get(tid)
            tenant = task.spec.tenant_id if task else "default"
        try:
            ref, spill = c.store.record(
                wid, size, producer_task=tid, ref_id=f"obj-{tid}",
                tenant=tenant,
                capability=Capability.grant_for_tenant(
                    c.token, tenant, f"obj-{tid}", "put"))
        except Exception as e:  # noqa: BLE001 -- quota reject etc.: the
            # task must *fail visibly*, not sit RUNNING forever
            self._fail_task(tid, wid, f"{type(e).__name__}: {e}")
            return {"ok": True, "stored": False}
        with c._lock:
            c.scheduler.on_task_finished(tid, ref, worker_id=wid)
        ev = c._futures.get(tid)
        if ev:
            ev.set()
        return {"ok": True, "stored": True, "spill": spill}

    def _handle_error(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        c = self.cluster
        with c._lock:
            c.scheduler.on_task_failed(msg["task"], msg["err"],
                                       worker_id=msg.get("worker"))
        return {"ok": True}

    def _handle_metric_deltas(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Fold a worker's piggybacked metric deltas into the head's
        aggregates (dict arithmetic only; the caller holds -- or this
        runs fine under -- the cluster lock). `deltas` are counter
        deltas folded into the per-worker aggregate dicts; `hists` are
        sparse histogram bucket deltas folded into the hub registry's
        cluster-wide histogram of the same name (bounds are fixed per
        name, so the fold is a pure element-wise add)."""
        deltas = msg.get("deltas")
        if deltas:
            agg = self._worker_metrics.setdefault(
                str(msg.get("worker", "")), {})
            get = agg.get
            for k, v in deltas.items():
                agg[k] = get(k, 0) + int(v)
        hists = msg.get("hists")
        if hists:
            cache = self._hist_cache
            for name, delta in hists.items():
                if isinstance(delta, dict):
                    h = cache.get(name)
                    if h is None:
                        h = self.metrics_hub.registry.histogram(str(name))
                        cache[name] = h
                    h.apply_delta(delta)
        return {"ok": True}

    def _handle_actor_result(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Worker-side completion report for one actor call (pure dict
        work: batch frames run it under the one cluster-lock pass)."""
        self._call_wait[str(msg["call"])] = time.perf_counter()
        self._actor_results[str(msg["call"])] = {
            "actor": msg.get("actor"), "host": msg.get("worker"),
            "value": msg.get("value"), "error": msg.get("error")}
        self._wake()
        return {"ok": True}

    def _handle_actor_exited(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Worker-side exit ack: the replica finished its in-flight work
        and unhosted -- only now does the scheduler release the actor's
        lifetime resource hold (and a drain of the node can complete).
        An ack carrying `error` reports a create that failed; its text
        answers later calls to that actor. Caller holds the cluster lock
        (top level or batch frame)."""
        aid = str(msg["actor"])
        released = self.cluster.scheduler.remove_actor(aid)
        self._actor_exits_asked.discard(aid)
        if msg.get("error"):
            self._actor_create_errors[aid] = str(msg["error"])
        return {"ok": True, "released": released}

    def _wake(self, worker_id: Optional[str] = None) -> None:
        """Something was queued for `worker_id`'s next poll (None: an
        actor result was stored): wake the requests held for it."""
        with self._arrivals:
            if worker_id is not None:
                self._arrived[worker_id] = self._arrived.get(worker_id, 0) + 1
            self._arrivals.notify_all()

    def _hold_empty_poll(self, wid: str
                         ) -> Optional[Tuple[float, float, int]]:
        """Hold a poll whose reply would carry nothing -- no task, no
        actor directive, no drain move, not draining -- until something
        is queued for `wid` or `POLL_HOLD_S` runs out. Returns the wait's
        (start, end, woke), woke 1 when an arrival ended it, or None
        where the poll is not held."""
        c = self.cluster
        with self._arrivals:
            seen = self._arrived.get(wid, 0)
        with c._lock:
            w = c.scheduler.workers.get(wid)
            if (self._closing or w is None or w.draining
                    or self._outbox.get(wid) or self._actor_outbox.get(wid)
                    or (wid in self._blob_eps
                        and self._pending_migrations.get(wid))):
                return None
        start = time.perf_counter()
        with self._arrivals:
            self._arrivals.wait_for(
                lambda: self._closing or self._arrived.get(wid, 0) != seen,
                self.POLL_HOLD_S)
            woke = int(self._arrived.get(wid, 0) != seen)
        return start, time.perf_counter(), woke

    def _await_result(self, call_id: str) -> None:
        """Hold a client's actor_result until `call_id`'s result is
        stored, at most `RESULT_WAIT_CAP_S`."""
        with self._arrivals:
            self._arrivals.wait_for(
                lambda: self._closing or call_id in self._actor_results,
                self.RESULT_WAIT_CAP_S)

    def dispatch(self, msg: Dict[str, Any],
                 hold: bool = False) -> Dict[str, Any]:
        """Serve one verified message. `hold` (the TCP handler's requests)
        lets an empty poll or a client's actor_result wait for what it
        asks for; in-process callers are never held."""
        op = msg.get("op")
        c = self.cluster
        if op == "join":
            wid = msg.get("worker") or f"tcp-{uuid.uuid4().hex[:6]}"
            self._outbox.setdefault(wid, [])
            plane = "relay"
            if (self.data_plane == "p2p" and msg.get("blob_port")
                    and msg.get("blob_host")):
                # p2p worker: the head holds only a metadata proxy; the
                # blobs stay on (and are served by) the worker itself
                self._blob_eps[wid] = (str(msg["blob_host"]),
                                       int(msg["blob_port"]))
                c.store.register_node(RemoteNodeStore(
                    wid, self._blob_eps[wid], c.token))
                plane = "p2p"
            else:
                store = NodeStore(wid)  # head-side store for relay workers
                c.store.register_node(store)
            with c._lock:
                c.scheduler.add_worker(
                    WorkerInfo(wid, msg.get("resources", {"cpu": 1.0})))
            return {"ok": True, "worker": wid, "data_plane": plane}
        if op == "poll":
            wid = msg["worker"]
            wait = self._hold_empty_poll(wid) if hold else None
            with c._lock:
                c.scheduler.heartbeat(wid)
                w = c.scheduler.workers.get(wid)
                draining = bool(w and w.draining)
            # PREPAREd drain-move directives ride the poll reply: the
            # source executes the pushes itself, so the head hands out
            # metadata only. Popped only for p2p workers (relay workers
            # never receive directives -- _migrate_directive routes them
            # to the head-side copy path, and popping here would drop
            # the batch on a reply path that cannot carry it); the
            # timeout clock restarts at delivery, so a slow poll does
            # not burn the push window (dict.pop is atomic; directives
            # re-queue via the abort/re-plan sweep if the worker dies)
            p2p = wid in self._blob_eps
            # popped under the cluster lock: _migrate_directive appends
            # under it, and an unlocked pop could orphan a directive
            # appended between the pop and the append's setdefault
            if p2p:
                with c._lock:
                    moves = self._pending_migrations.pop(wid, [])
            else:
                moves = []
            if moves:
                # directives whose move was aborted/re-planned since they
                # were queued (timeout sweep, destination death) are
                # dropped here instead of burning a redundant fat push
                moves = [m for m in moves
                         if c.store.move_in_flight(m["ref"])
                         == (wid, m["node"])]
            if moves:
                with c._lock:
                    for mv in moves:
                        c.scheduler.note_move_dispatched(wid, mv["ref"])
            # actor lifecycle directives ride the poll reply exactly like
            # drain moves. A draining host asks each replica to exit
            # (once): the drain completes only after every exit is acked,
            # so scale-down never cuts off an in-flight decode.
            with c._lock:
                if draining:
                    for aid in c.scheduler.actors_on(wid):
                        if aid not in self._actor_exits_asked:
                            self._actor_exits_asked.add(aid)
                            self._actor_outbox.setdefault(wid, []).append(
                                {"op": "actor_exit", "actor": aid})
                acts = self._actor_outbox.pop(wid, [])
                handed = [(a["call"], self._call_wait.pop(a["call"], None))
                          for a in acts if a.get("op") == "actor_call"]
            now = time.perf_counter()
            for call_id, queued in handed:
                if queued is not None:
                    SPANS.record("head.outbox", queued, now, call=call_id)
            if wait is not None:
                SPANS.record("head.poll_wait", wait[0], wait[1],
                             woke=wait[2], calls=[cid for cid, _ in handed])

            def with_moves(reply: Dict[str, Any]) -> Dict[str, Any]:
                if moves:
                    reply["migrations"] = moves
                if acts:
                    reply["actor_ops"] = acts
                if wait is not None:
                    # the worker needs no pause after a poll the head held
                    # (and leaves the hold out of its poll latency)
                    reply["waited"] = wait[1] - wait[0]
                return reply

            box = self._outbox.get(wid, [])
            if not box:
                # a drained worker with an empty queue may exit: the head
                # finishes the drain once migrations land and tasks stop
                return with_moves({"ok": True, "task": None,
                                   "draining": draining})
            tid = box.pop(0)
            with c._lock:
                task = c.scheduler.graph.tasks[tid]
                tenant = task.spec.tenant_id
            if p2p:
                try:
                    # metadata-only dispatch: control payload + dep
                    # locations/tickets; the worker pulls the bytes peer
                    # to peer. Built OUTSIDE the cluster lock -- the
                    # head-staging fallback may do a real transfer, and
                    # data-plane I/O must never stall the control plane
                    # (the store has its own lock)
                    return with_moves(
                        {"ok": True, "task": tid,
                         "payload": _enc((task.spec.fn, task.spec.args,
                                          task.spec.kwargs)),
                         "deps": self._deps_meta(task, wid, tenant),
                         "tenant": tenant, "draining": draining})
                except Exception as e:  # noqa: BLE001
                    self._fail_task(tid, wid, f"{type(e).__name__}: {e}")
                    return with_moves({"ok": True, "task": None,
                                       "draining": draining})
            with c._lock:
                try:
                    # relay: deps are resolved head-side *as the task's
                    # tenant*: a task whose deps point at another tenant's
                    # objects fails here -- as a *task failure*, not a
                    # stranded RUNNING task (the worker just keeps
                    # polling). Relay stores live in this process, so
                    # these are memory copies, safe under the lock.
                    payload = _enc(
                        (task.spec.fn, task.spec.args, task.spec.kwargs,
                         [c.store.get(
                             "head", d,
                             capability=Capability.grant_for_tenant(
                                 c.token, tenant, d.id, "get"))
                          for d in task.deps]))
                    self.head_payload_bytes += sum(
                        c.store.size_of(d) for d in task.deps)
                except Exception as e:  # noqa: BLE001
                    c.scheduler.on_task_failed(
                        tid, f"{type(e).__name__}: {e}", worker_id=wid)
                    ev = c._futures.get(tid)
                    if ev:
                        ev.set()
                    return with_moves({"ok": True, "task": None,
                                       "draining": draining})
            return with_moves({"ok": True, "task": tid, "payload": payload,
                               "tenant": tenant, "draining": draining})
        if op == "result_meta":
            return self._handle_result_meta(msg)
        if op == "result":
            tid, wid = msg["task"], msg["worker"]
            value = _dec(msg["payload"])
            with c._lock:
                task = c.scheduler.graph.tasks.get(tid)
                tenant = task.spec.tenant_id if task else "default"
            try:
                ref = c.store.put("head", value, producer_task=tid,
                                  ref_id=f"obj-{tid}", tenant=tenant)
            except Exception as e:  # noqa: BLE001 -- e.g. quota reject: the
                # task must *fail visibly*, not sit RUNNING forever
                self._fail_task(tid, wid, f"{type(e).__name__}: {e}")
                return {"ok": True, "stored": False}
            with c._lock:
                # counter writes stay under the cluster lock: handler
                # threads run concurrently and += is not atomic
                self.head_payload_bytes += ref.size
                c.scheduler.on_task_finished(tid, ref, worker_id=wid)
            ev = c._futures.get(tid)
            if ev:
                ev.set()
            return {"ok": True}
        if op == "error":
            return self._handle_error(msg)
        if op == "metric_deltas":
            with c._lock:
                return self._handle_metric_deltas(msg)
        if op == "leave":
            # idle-exit handshake: a worker may only walk away once no hot
            # object's last copy lives on it. The head hands back p2p push
            # assignments (peer blob servers, put tickets) for the at-risk
            # blobs; the worker replicates, reports `pushed`, and re-asks.
            wid = msg["worker"]
            with c._lock:
                w = c.scheduler.workers.get(wid)
                if w is None:
                    return {"ok": True, "exit": True}
                if w.running or w.actors:
                    # a live replica actor is never idle cover: the host
                    # must not walk away between request bursts
                    return {"ok": True, "exit": False, "replicate": []}
                at_risk = self._at_risk_objects(wid)
                if at_risk and wid not in self._blob_eps:
                    # relay worker: its "node store" lives in THIS process
                    # (results were relayed), so the head migrates the
                    # blobs itself -- asking the worker to push bytes it
                    # never held would refuse the exit forever
                    for ref in at_risk:
                        try:
                            c.store.migrate(ref, wid, "head")
                        except Exception:  # noqa: BLE001 -- keep refusing
                            pass
                    at_risk = self._at_risk_objects(wid)
                if not at_risk:
                    ok = c.scheduler.retire_worker(wid)
                    if ok:
                        self._outbox.pop(wid, None)
                        self._blob_eps.pop(wid, None)
                        self._pending_migrations.pop(wid, None)
                    return {"ok": True, "exit": bool(ok)}
                if wid not in self._blob_eps:
                    # relay worker whose blobs could not be migrated (e.g.
                    # a tenant-scoped guard): nothing the worker itself can
                    # push -- release it and degrade to drop + lineage,
                    # exactly like a drain would, rather than livelock
                    ok = c.scheduler.retire_worker(wid)
                    if ok:
                        self._outbox.pop(wid, None)
                    return {"ok": True, "exit": bool(ok), "replicate": []}
                moves = self._replication_plan(wid, at_risk)
            return {"ok": True, "exit": False, "replicate": moves}
        if op == "actor_create":
            # place a long-running replica actor: the scheduler acquires
            # its resources for the actor's LIFETIME (placement-group
            # aware), and the hosting worker instantiates it from the
            # actor_create directive riding its next poll reply
            aid = str(msg.get("actor") or f"actor-{uuid.uuid4().hex[:6]}")
            tenant = str(msg.get("tenant") or "default")
            factory = str(msg["factory"])
            with c._lock:
                try:
                    wid = c.scheduler.place_actor(
                        aid, msg.get("resources") or {"cpu": 1.0}, tenant,
                        msg.get("placement_group"), msg.get("bundle_index"))
                except ValueError as e:
                    return {"ok": False, "error": str(e)}
                if wid is None:
                    return {"ok": False,
                            "error": f"no worker fits actor {aid!r}"}
                self._actor_outbox.setdefault(wid, []).append(
                    {"op": "actor_create", "actor": aid, "factory": factory,
                     "kwargs": msg.get("kwargs") or {}, "tenant": tenant})
                self._wake(wid)
            cap = Capability.grant_actor(c.token, tenant, aid)
            return {"ok": True, "actor": aid, "worker": wid,
                    "cap": {"object_id": cap.object_id, "right": cap.right,
                            "mac": cap.mac, "tenant_id": cap.tenant_id}}
        if op == "actor_call":
            # route one request to a replica -- verified against the
            # actor-scoped capability BEFORE anything is queued
            aid = str(msg["actor"])
            with c._lock:
                info = c.scheduler.actors.get(aid)
                why = self._actor_create_errors.get(aid)
            if info is None:
                err = f"unknown actor {aid!r}"
                if why:
                    err = f"actor {aid!r} failed to start: {why}"
                return {"ok": False, "error": err}
            cd = msg.get("cap") or {}
            cap = Capability(str(cd.get("object_id", "")),
                             str(cd.get("right", "")),
                             str(cd.get("mac", "")),
                             str(cd.get("tenant_id", "default")))
            try:
                cap.verify_actor(c.token, aid, info.tenant_id)
            except SecurityError as e:
                return {"ok": False, "error": str(e)}
            call_id = str(msg.get("call") or f"call-{uuid.uuid4().hex[:8]}")
            with c._lock:
                self._call_wait[call_id] = time.perf_counter()
                self._actor_outbox.setdefault(info.worker_id, []).append(
                    {"op": "actor_call", "actor": aid, "call": call_id,
                     "payload": msg.get("payload")})
                self._wake(info.worker_id)
            return {"ok": True, "call": call_id, "worker": info.worker_id}
        if op == "actor_result":
            if msg.get("worker"):      # worker-side completion report
                with c._lock:
                    return self._handle_actor_result(msg)
            call_id = str(msg["call"])
            if hold:
                self._await_result(call_id)
            res = self._actor_results.pop(call_id, None)
            if res is None:
                return {"ok": True, "done": False}
            held = self._call_wait.pop(call_id, None)
            if held is not None:
                SPANS.record("head.held", held, time.perf_counter(),
                             call=call_id)
            return dict({"ok": True, "done": True}, **res)
        if op == "actor_exit":
            aid = str(msg["actor"])
            if msg.get("worker"):      # worker-side exit ack
                with c._lock:
                    return self._handle_actor_exited(msg)
            with c._lock:
                info = c.scheduler.actors.get(aid)
            if info is None:
                return {"ok": True, "exited": True}
            cd = msg.get("cap") or {}
            cap = Capability(str(cd.get("object_id", "")),
                             str(cd.get("right", "")),
                             str(cd.get("mac", "")),
                             str(cd.get("tenant_id", "default")))
            try:
                cap.verify_actor(c.token, aid, info.tenant_id)
            except SecurityError as e:
                return {"ok": False, "error": str(e)}
            with c._lock:
                if aid not in self._actor_exits_asked:
                    self._actor_exits_asked.add(aid)
                    self._actor_outbox.setdefault(info.worker_id,
                                                  []).append(
                        {"op": "actor_exit", "actor": aid})
                    self._wake(info.worker_id)
            return {"ok": True, "exited": False}
        if op == "ticket":
            # mid-fetch re-mint: a task with many fat deps can outlive the
            # tickets batched into its poll reply -- the worker asks for a
            # fresh descriptor per remaining dep (same tenant checks)
            wid, tid = msg["worker"], msg.get("task", "")
            with c._lock:
                task = c.scheduler.graph.tasks.get(tid)
                tenant = task.spec.tenant_id if task else None
            if tenant is None:
                return {"ok": False, "error": f"unknown task {tid!r}"}
            try:
                ref = ObjectRef(str(msg["object"]))
                return {"ok": True, "dep": self._dep_meta(ref, wid, tenant)}
            except SecurityError as e:
                return {"ok": False, "error": str(e)}
        if op == "tickets":
            # batched mid-fetch re-mint: one round trip refreshes every
            # dep the worker still needs. Each dep gets its OWN verdict
            # (aligned 1:1 with `objects`): one expired or denied dep
            # must not re-mint deps that already landed, nor fail the
            # whole batch. May stage head copies (`_dep_meta` fallback),
            # so this handler never runs under the cluster lock.
            wid, tid = msg["worker"], msg.get("task", "")
            with c._lock:
                task = c.scheduler.graph.tasks.get(tid)
                tenant = task.spec.tenant_id if task else None
            if tenant is None:
                return {"ok": False, "error": f"unknown task {tid!r}"}
            deps: List[Dict[str, Any]] = []
            for oid in msg.get("objects", []):
                try:
                    deps.append({"ok": True,
                                 "dep": self._dep_meta(
                                     ObjectRef(str(oid)), wid, tenant)})
                except Exception as e:  # noqa: BLE001 -- per-dep verdict
                    deps.append({"ok": False,
                                 "error": f"{type(e).__name__}: {e}"})
            return {"ok": True, "deps": deps}
        if op == "pushed":
            # a worker registering its OWN cache is trusted at the same
            # level as its result_meta size claims (sealed envelope, its
            # bytes, its node) -- no probe on the hot dep-cache path.
            # Third-party claims ("node X now holds it") are probed before
            # the directory (and thus drain cover) believes them.
            if msg.get("worker") == msg["node"]:
                c.store.note_replica(msg["object"], msg["node"])
                return {"ok": True}
            ok = c.store.confirm_replica(msg["object"], msg["node"])
            return {"ok": ok}
        if op == "migrated":
            # destination ack for one direct drain push -- the
            # result_meta of the migrate protocol. Only now does the head
            # COMMIT the directory's owner handoff; the commit also
            # deletes the source's copy (a control-sized `del`, zero
            # payload through the head).
            wid, oid = msg["worker"], str(msg["object"])
            mv = c.store.move_in_flight(oid)
            if mv is None:
                # the move was already aborted (timeout sweep) or its
                # source died mid-drain: a landed push is still a real
                # copy -- probe before believing (same rule as
                # third-party `pushed` claims), then wake any tasks the
                # apparent loss parked
                if c.store.confirm_replica(oid, wid):
                    with c._lock:
                        for t in c.scheduler.graph.object_available(
                                ObjectRef(oid)):
                            c.scheduler._enqueue_ready(t)
                        c.scheduler.schedule()
                    return {"ok": True, "committed": False,
                            "recovered": True}
                # the object was released mid-move: the landed copy is
                # garbage -- purge it so it does not squat in the
                # destination's store with no directory entry to GC it
                c.store.purge_copy(oid, wid)
                return {"ok": True, "committed": False}
            src, dst = mv
            if wid != dst:
                # a STALE directive's push landed somewhere the current
                # (re-planned) move no longer points: register the probed
                # copy as an ordinary replica so the bytes stay
                # directory-tracked -- and GC-able on release -- instead
                # of leaking unrecorded in the old destination's store
                replica = c.store.confirm_replica(oid, wid)
                return {"ok": True, "committed": False, "replica": replica}
            # commit OUTSIDE the cluster lock: it may issue the ticketed
            # `del` of the source's copy over TCP
            committed = c.store.commit_move(oid, src, dst)
            if committed:
                with c._lock:
                    c.scheduler.note_migrated(src, ObjectRef(oid))
            return {"ok": True, "committed": committed}
        if op == "migrate_failed":
            # source-side push failure report. Probe-first abort: a push
            # that landed right before a timed-out reply is promoted to a
            # COMMIT. A *retryable* transport fault (after the worker's
            # own bounded retry) degrades to the head-relay copy -- never
            # to lineage reconstruction while the head is healthy;
            # anything else re-plans toward a fresh destination + ticket.
            wid, oid = msg["worker"], str(msg["object"])
            mv = c.store.move_in_flight(oid)
            if mv is None or mv[0] != wid:
                return {"ok": True}
            src, dst = mv
            ref = ObjectRef(oid)
            if c.store.abort_move(oid, probe=True):
                with c._lock:
                    c.scheduler.note_migrated(src, ref)
                return {"ok": True, "committed": True}
            if msg.get("retryable"):
                c.store.stats["relay_fallbacks"] += 1
                with c._lock:
                    # the relay copy starts NOW: restart the move's
                    # timeout clock so a long transfer is not aborted
                    # against a window that began at plan time
                    c.scheduler.note_move_dispatched(src, oid)
                self._migrate_relay(src, ref, dst)
                return {"ok": True, "fallback": "relay"}
            with c._lock:
                c.scheduler.note_migration_failed(src, ref)
                c.scheduler._dispatch_moves(src)
            return {"ok": True}
        if op == "drain":
            # eviction notice for a remote worker: the outer resource
            # manager (or an operator) asks the head to retire this node
            wid = msg["worker"]
            with c._lock:
                ok = c.scheduler.begin_drain(wid, msg.get("deadline_s"))
            return {"ok": ok, "worker": wid}
        if op == "drain_status":
            wid = msg["worker"]
            with c._lock:
                complete = c.scheduler.drain_complete(wid)
                if complete:
                    c.scheduler.finish_drain(wid)
            if complete:
                # the worker exits on this reply: nothing will ever poll
                # its remaining directives out of the queue
                self._pending_migrations.pop(wid, None)
            return {"ok": True, "worker": wid, "complete": complete}
        if op == "stats":
            with c._lock:
                return {"ok": True, "stats": dict(c.scheduler.stats),
                        "tenants": c.scheduler.tenant_shares()}
        if op == "batch":
            # one wire frame, ONE cluster-lock acquisition for the
            # lock-bound sub-ops a worker queued between polls
            # (result_meta / error / own-cache pushed / metric_deltas).
            # Sub-ops that may do data-plane staging I/O (the poll riding
            # last, ticket re-mints) are deferred OUTSIDE the lock and
            # served by their normal handlers. Replies align 1:1 with
            # ops; each sub-op carries its own verdict.
            subs = msg.get("ops") or []
            replies: List[Optional[Dict[str, Any]]] = [None] * len(subs)
            deferred: List[int] = []
            with c._lock:
                for i, sub in enumerate(subs):
                    sop = sub.get("op") if isinstance(sub, dict) else None
                    try:
                        if sop == "result_meta":
                            replies[i] = self._handle_result_meta(sub)
                        elif sop == "error":
                            replies[i] = self._handle_error(sub)
                        elif sop == "metric_deltas":
                            replies[i] = self._handle_metric_deltas(sub)
                        elif (sop == "pushed"
                              and sub.get("worker") == sub.get("node")):
                            # own-cache claim: trusted without a probe
                            # (same rule as the top-level handler) --
                            # pure directory work, safe under the lock
                            c.store.note_replica(str(sub["object"]),
                                                 str(sub["node"]))
                            replies[i] = {"ok": True}
                        elif sop == "actor_result" and sub.get("worker"):
                            # a replica's finished call (dict work only)
                            replies[i] = self._handle_actor_result(sub)
                        elif sop == "actor_exit" and sub.get("worker"):
                            # a replica's exit ack: releases the actor's
                            # lifetime resource hold under this same pass
                            replies[i] = self._handle_actor_exited(sub)
                        elif sop == "batch":
                            replies[i] = {"ok": False,
                                          "error": "nested batch refused"}
                        else:
                            deferred.append(i)
                    except Exception as e:  # noqa: BLE001 -- per-sub
                        # verdict: one bad ack must not poison the frame
                        replies[i] = {"ok": False,
                                      "error": f"{type(e).__name__}: {e}"}
            for i in deferred:
                try:
                    replies[i] = self.dispatch(subs[i], hold)
                except Exception as e:  # noqa: BLE001
                    replies[i] = {"ok": False,
                                  "error": f"{type(e).__name__}: {e}"}
            return {"ok": True, "replies": replies}
        if op == "metrics":
            # the scaling signals the K8s custom-metrics adapter republishes
            # for the HorizontalPodAutoscaler (backends/kubernetes.py), plus
            # the observability plane's counters/percentiles -- all built by
            # the ONE builder the chaos conformance checker cross-examines
            return self._build_metrics()
        if op == "metrics_text":
            # Prometheus text exposition: the same flat snapshot rendered
            # with the hub registry's histogram families (_bucket layout)
            flat = self._build_metrics()
            return {"ok": True,
                    "text": render_prometheus(self.metrics_hub.registry,
                                              flat=flat)}
        if op == "dashboards":
            return {"ok": True, "dashboards": render_dashboards()}
        return {"ok": False, "error": f"unknown op {op!r}"}

    def _build_metrics(self) -> Dict[str, Any]:
        """Snapshot scheduler-derived values under the cluster lock,
        then build the flat metrics reply outside it (store reads take
        their own shard locks) and record it into the hub's ring-buffer
        time series."""
        c = self.cluster
        with c._lock:
            workers = [w for w in c.scheduler.workers.values() if w.alive]
            busy = sum(1 for w in workers if w.running)
            backlog = sum(
                1 for t in c.scheduler.graph.tasks.values()
                if t.state in (TaskState.READY, TaskState.PENDING))
            by_tenant = c.scheduler.backlog_by_tenant()
            shares = c.scheduler.tenant_shares()
            wm = {k: dict(m) for k, m in self._worker_metrics.items()}
            replica_count = len(c.scheduler.actors)
            serve = dict(self.serve_stats)
        out = build_cluster_metrics(
            c.store, c.scheduler, worker_metrics=wm, serve_stats=serve,
            replica_count=replica_count, workers=len(workers), busy=busy,
            backlog=backlog, backlog_by_tenant=by_tenant, shares=shares)
        self.metrics_hub.ingest(time.time(), out)
        return out

    def _at_risk_objects(self, wid: str) -> List[ObjectRef]:
        """Hot objects whose only copy sits on `wid` (caller holds the
        cluster lock). Same hotness rule as the drain planner."""
        c = self.cluster
        active = (TaskState.PENDING, TaskState.READY, TaskState.RUNNING)
        hot_deps = {d.id for t in c.scheduler.graph.tasks.values()
                    if t.state in active for d in t.deps}
        return [ref for oid, ref in c.store.objects_on(wid).items()
                if c.store.sole_holder(ref, wid)
                and (c.store.refcount(oid) > 0 or oid in hot_deps)]

    def _replication_plan(self, wid: str,
                          at_risk: List[ObjectRef]) -> List[Dict[str, Any]]:
        """Push assignments for a leaving worker's at-risk blobs: each goes
        to the peer (or the head's blob server) with the least-loaded
        link, authorized by a put ticket bound to the pushing worker."""
        c = self.cluster
        peers = sorted((p for p in self._blob_eps if p != wid
                        and c.store.has_node(p)),
                       key=lambda p: (c.store.link_load(p), p))
        moves = []
        for ref in at_risk:
            dst = peers[0] if peers else "head"
            ep = self._source_endpoints(dst)
            if ep is None:
                continue               # nowhere to push: keep refusing exit
            tenant = c.store.tenant_of(ref.id) or ref.tenant
            ticket = TransferTicket.grant(c.token, ref.id, dst, wid,
                                          tenant, "put",
                                          ttl_s=max(self.ticket_ttl_s, 60.0))
            moves.append({"ref": ref.id, "node": dst,
                          "host": ep[0], "port": ep[1],
                          "ticket": ticket.to_wire()})
            if peers:
                peers.append(peers.pop(0))   # rotate: spread the pushes
        return moves

    def launch(self, task, worker_id: str):
        self._outbox.setdefault(worker_id, []).append(task.id)
        self._wake(worker_id)

    def attach(self):
        """Route scheduler launches for tcp- workers through the outbox."""
        orig = self.cluster.scheduler.launch_fn

        def launch(task, worker_id):
            if worker_id.startswith("tcp-") or worker_id in self._outbox:
                self.launch(task, worker_id)
            else:
                orig(task, worker_id)
        self.cluster.scheduler.launch_fn = launch

    def shutdown(self):
        with self._arrivals:           # nothing held outlives the head
            self._closing = True
            self._arrivals.notify_all()
        self.server.shutdown()
        self.server.server_close()   # release the listening socket fd
        if self._blob_srv is not None:
            self._blob_srv.shutdown()


def run_worker(rendezvous_dir: str, cluster_id: str, worker_id: str = "",
               max_idle_s: float = 30.0, data_plane: str = "p2p",
               blob_host: str = "127.0.0.1",
               capacity_bytes: int = 256 << 20,
               spill_dir: Optional[str] = None,
               actor_factories: Optional[Dict[str, Callable[..., Any]]]
               = None,
               flush_metrics_on_exit: bool = True,
               metrics_every: int = 4,
               metrics_truth: Optional[Dict[str, int]] = None):
    """Worker main loop. In the default p2p data plane the worker runs a
    blob server over its local NodeStore, pulls dependencies peer-to-peer
    with head-minted transfer tickets, and registers results by metadata
    only. `data_plane="relay"` (or a head running in relay mode) falls
    back to the legacy everything-through-the-head protocol.

    Idle-exit safety: the idle clock resets on task *completion* (a long
    task must not count toward idleness), and the worker refuses to exit
    -- even past `max_idle_s` -- until the head confirms no hot object's
    last copy lives here (`leave` handshake, replicating blobs to peers
    first if needed). A worker hosting live service actors never starts
    the leave handshake at all: a replica between request bursts is not
    idle.

    `actor_factories` names the service-actor types this worker can host
    (factory name -> callable returning an object with
    ``handle(payload) -> value`` and optionally ``drain()``). Lifecycle
    directives arrive on the poll reply's `actor_ops` list; results and
    exit acks ride the next poll's batch frame.

    Observability: counter deltas (blob-server stats, spill-tier stats,
    drain-push counters) and histogram bucket deltas (poll round-trip
    latency) piggyback on the poll batch frame -- zero extra wire frames
    (the obs benchmark gates this). They accrue worker-side and ride
    every `metrics_every`-th poll (the telemetry cadence: the head folds
    1/k as often, bounding collection overhead on its hot path; nothing
    is lost in between, the deltas just wait). Deltas accrued after the
    last flush are sent in one final `metric_deltas` frame during the
    drain / leave handshake; `flush_metrics_on_exit=False` disables that
    flush (test hook -- the conformance checker must catch the loss).
    `metrics_truth`, when given, is continuously updated with this
    worker's live counter values: the ground truth the conformance
    checker holds the head's aggregates against."""
    rdv = FileRendezvous(rendezvous_dir)
    ep = rdv.wait(cluster_id, timeout=60.0)
    token = ep.token
    nonces = NonceCache()        # head replies are replay-protected too
    tenants: Dict[str, str] = {}   # object id -> tenant (blobs held here)
    # lock-bound acks queued between polls -- each entry is (op dict,
    # apply(reply) callback or None). They ride the next poll as ONE
    # `batch` frame: a result or error report no longer costs its own
    # round trip, and a transient send failure keeps them queued (the
    # head's record/stale-report guards make a replayed ack idempotent)
    pending_ops: List[Tuple[Dict[str, Any],
                            Optional[Callable[[Optional[Dict[str, Any]]],
                                              None]]]] = []
    # last blob-server counters already reported to the head: the next
    # batch carries only the deltas, advanced after a confirmed send
    metric_base: Dict[str, int] = {"serves": 0, "receives": 0,
                                   "served_bytes": 0, "batched_moves": 0,
                                   "delta_spill_bytes_saved": 0,
                                   "promotions": 0,
                                   "drain_pushed_blobs": 0,
                                   "drain_pushed_bytes": 0}
    # worker-local counters with no store/blob-server home: drain-push
    # work accrues here (between the poll that delivered the directives
    # and exit -- exactly the window the exit flush exists for)
    wstats: Dict[str, int] = {"drain_pushed_blobs": 0,
                              "drain_pushed_bytes": 0}
    # poll round-trip latency histogram: bucket deltas ride the same
    # metric_deltas sub-op; base advances only after a confirmed send
    poll_hist = Histogram()
    poll_hist_base = Histogram()
    blob_srv: Optional[BlobServer] = None
    own_spill: Optional[str] = None
    join_msg: Dict[str, Any] = {"op": "join", "worker": worker_id,
                                "resources": {"cpu": 1.0}}
    if data_plane == "p2p" and spill_dir is None:
        # relay workers never touch the local store -- only the p2p plane
        # needs a spill dir, and one we made we also clean up on exit
        spill_dir = own_spill = tempfile.mkdtemp(prefix="syndeo-blob-")
    local = NodeStore(worker_id or f"pending-{uuid.uuid4().hex[:6]}",
                      capacity_bytes=capacity_bytes, spill_dir=spill_dir)
    if data_plane == "p2p":
        blob_srv = BlobServer(local, token, host=blob_host,
                              tenant_of=tenants.get,
                              on_delete=lambda oid: tenants.pop(oid, None))
        join_msg["blob_host"] = blob_host
        join_msg["blob_port"] = blob_srv.port
    joined = _request(ep.host, ep.port, token, join_msg, nonce_cache=nonces)
    wid = joined["worker"]
    local.node_id = wid            # assigned id names the store (spill files)

    def live_metric(k: str) -> int:
        """Current ground-truth value of one piggybacked counter: spill
        keys live on the node store, drain-push keys on wstats, the rest
        on the blob server."""
        if k in ("delta_spill_bytes_saved", "promotions"):
            return int(local.stats.get(k, 0))
        if k in wstats:
            return wstats[k]
        return (int(blob_srv.stats.get(k, 0))
                if blob_srv is not None else 0)

    def compute_deltas() -> Dict[str, int]:
        if blob_srv is None:
            return {}                # relay plane: no local data plane
        return {k: live_metric(k) - metric_base[k]
                for k in metric_base if live_metric(k) != metric_base[k]}

    def update_truth():
        if metrics_truth is None:
            return
        for k in metric_base:
            metrics_truth[k] = live_metric(k)
        metrics_truth["polls"] = poll_hist.count

    def flush_metrics():
        """Exit-path flush: deltas accrued since the last confirmed poll
        (drain pushes, the final polls' latencies) would die with this
        worker -- send them as ONE final metric_deltas frame during the
        drain/leave handshake. Disabled (`flush_metrics_on_exit=False`)
        only so tests can prove the conformance checker catches the
        resulting head-vs-reality divergence."""
        update_truth()
        if not flush_metrics_on_exit:
            return
        deltas = compute_deltas()
        hd = poll_hist.to_delta(poll_hist_base)
        if not deltas and not hd["count"]:
            return
        msg: Dict[str, Any] = {"op": "metric_deltas", "worker": wid,
                               "deltas": deltas}
        if hd["count"]:
            msg["hists"] = {"syndeo_worker_poll_seconds": hd}
        try:
            _request(ep.host, ep.port, token, msg, nonce_cache=nonces)
        except Exception:  # noqa: BLE001 -- head gone: nothing left to
            return         # reconcile against anyway
        for k, v in deltas.items():
            metric_base[k] += v
        poll_hist_base.apply_delta(hd)

    def ack_migration(oid: str, tenant: str):
        """Destination-side metadata ack (the migrate protocol's
        result_meta): a drain push just landed in our local store --
        adopt its tenant and tell the head, which COMMITs the owner
        handoff. A lost ack is recovered by the head's probe-on-timeout."""
        tenants[oid] = tenant
        try:
            _request(ep.host, ep.port, token,
                     {"op": "migrated", "worker": wid, "object": oid},
                     nonce_cache=nonces)
        except Exception:  # noqa: BLE001 -- head sweep probes + commits
            pass

    def ack_migrations(landed: List[Tuple[str, str]]):
        """Batched destination-side ack: every blob of one multi-blob
        push frame that landed under a migrate-right ticket commits
        through ONE `batch` control frame of `migrated` sub-ops instead
        of one round trip each. A lost frame is recovered move-by-move
        by the head's probe-on-timeout sweep."""
        for oid, tenant in landed:
            tenants[oid] = tenant
        if len(landed) == 1:
            ack_migration(*landed[0])
            return
        ops = [{"op": "migrated", "worker": wid, "object": oid}
               for oid, _tenant in landed]
        try:
            _request(ep.host, ep.port, token,
                     {"op": "batch", "worker": wid, "ops": ops},
                     nonce_cache=nonces)
        except Exception:  # noqa: BLE001 -- head sweep probes + commits
            pass

    if blob_srv is not None:
        blob_srv.on_migrate = ack_migration
        blob_srv.on_migrate_many = ack_migrations

    def report_move_failures(failures: List[Tuple[str, bool, str]]):
        """Tell the head which moves failed -- ONE frame even for a
        whole failed batch (retryable -> relay fallback, else ABORT +
        re-plan). Losing it is safe: the timeout sweep aborts anyway."""
        if not failures:
            return
        ops = [{"op": "migrate_failed", "worker": wid, "object": oid,
                "retryable": retryable, "err": err}
               for oid, retryable, err in failures]
        req = (ops[0] if len(ops) == 1
               else {"op": "batch", "worker": wid, "ops": ops})
        try:
            _request(ep.host, ep.port, token, req, nonce_cache=nonces)
        except Exception:  # noqa: BLE001 -- the head's timeout
            pass           # sweep aborts + re-plans anyway

    def run_migrations(moves: List[Dict[str, Any]]):
        """Source-side executor for the head's direct-push drain
        directives. Moves sharing a destination coalesce into ONE
        connection carrying ONE multi-blob push frame with per-blob
        verdicts (the control plane's `batch` idiom applied to the blob
        plane): a drain plan of many small objects pays one connect +
        one ack round trip per destination instead of per object.
        Destinations are served deadline-soonest-first so a
        preemption-driven drain races its eviction notice. Success is
        acked by the *destination*; failures are reported (batched) so
        the head can fall back to the relay path (retryable) or ABORT +
        re-plan. The local copy is kept -- the head deletes it after
        COMMIT."""
        groups: Dict[Tuple[str, int, str], List[Dict[str, Any]]] = {}
        for mv in moves:
            groups.setdefault(
                (str(mv["host"]), int(mv["port"]), str(mv["node"])),
                []).append(mv)

        def urgency(grp: List[Dict[str, Any]]) -> float:
            ds = [float(mv["deadline_s"]) for mv in grp
                  if mv.get("deadline_s") is not None]
            return min(ds) if ds else float("inf")

        failures: List[Tuple[str, bool, str]] = []
        for (host, port, node), grp in sorted(
                groups.items(), key=lambda kv: urgency(kv[1])):
            transport = TCPTransport(
                lambda _n, _ep=(host, port): _ep, token, wid)
            items: List[Tuple[ObjectRef, bytes,
                              Optional[TransferTicket]]] = []
            for mv in grp:
                ref = ObjectRef(str(mv["ref"]), int(mv.get("size", 0)))
                try:
                    blob = local.export_blob(ref)
                except Exception as e:  # noqa: BLE001 -- KeyError (gone)
                    # but also e.g. an unreadable spill file: a failed
                    # export must degrade to a migrate_failed report,
                    # never kill a worker that still holds sole copies
                    # of the other drain objects
                    failures.append((ref.id, False,
                                     f"{type(e).__name__}: {e}"))
                    continue
                items.append((ref, blob,
                              TransferTicket.from_wire(mv["ticket"])))
            if not items:
                continue
            if len(items) == 1:
                ref, blob, ticket = items[0]
                err, retryable = push_with_retry(transport, node, ref,
                                                 blob, ticket)
                if err is not None:
                    failures.append((ref.id, retryable,
                                     f"{type(err).__name__}: {err}"))
                else:
                    wstats["drain_pushed_blobs"] += 1
                    wstats["drain_pushed_bytes"] += len(blob)
                continue
            verdicts, err, retryable = push_batch_with_retry(
                transport, node, items)
            if err is not None:
                failures.extend(
                    (ref.id, retryable, f"{type(err).__name__}: {err}")
                    for ref, _blob, _t in items)
                continue
            for (ref, blob, _t), v in zip(items, verdicts):
                if not v.get("ok"):
                    failures.append(
                        (ref.id, False, str(v.get("error", "refused"))))
                else:
                    wstats["drain_pushed_blobs"] += 1
                    wstats["drain_pushed_bytes"] += len(blob)
        report_move_failures(failures)

    def fetch_dep(meta: Dict[str, Any]) -> Tuple[bool, Any]:
        """One pass over a dep's ticketed sources: (True, value) when a
        fetch lands, (False, last error) when every source refused."""
        oid = meta["ref"]
        ref = ObjectRef(oid, int(meta.get("size", 0)))
        if local.has(ref):
            return True, pickle.loads(local.export_blob(ref))
        last_err: Optional[Exception] = None
        for src in meta.get("sources", []):
            try:
                ticket = (TransferTicket.from_wire(src["ticket"])
                          if src.get("ticket") else None)
                transport = TCPTransport(
                    lambda _n, _ep=(src["host"], int(src["port"])): _ep,
                    token, wid)
                blob = transport.fetch(src["node"], ref, ticket)
                local.put_blob(ref, blob)  # cache: later tasks hit local
                tenants[oid] = meta.get("tenant", "default")
                try:
                    # register the cached replica: the directory can
                    # now offer this node as a source, count it as
                    # drain cover, and -- critically -- delete it on
                    # release() (an unregistered cache would outlive
                    # its object)
                    _request(ep.host, ep.port, token,
                             {"op": "pushed", "worker": wid,
                              "object": oid, "node": wid},
                             nonce_cache=nonces)
                except OSError:
                    pass               # head unreachable: cache stays local
                return True, pickle.loads(blob)
            except Exception as e:  # noqa: BLE001 -- try the next source
                last_err = e
        return False, last_err

    def resolve_deps(metas: List[Dict[str, Any]], tid: str) -> List[Any]:
        """Fetch every dep once over its poll-time tickets, then re-mint
        ONLY the failed subset in a single batched `tickets` round trip
        and retry those. A long chain of fat deps used to cost one
        `ticket` call per expired dep; now the whole tail refreshes in
        one frame, and a dep that already landed is never re-minted."""
        values: List[Any] = [None] * len(metas)
        errors: Dict[int, Any] = {}
        for i, meta in enumerate(metas):
            ok, out = fetch_dep(meta)
            if ok:
                values[i] = out
            else:
                errors[i] = out
        if errors:
            failed = sorted(errors)
            try:
                fresh = _request(ep.host, ep.port, token,
                                 {"op": "tickets", "worker": wid,
                                  "task": tid,
                                  "objects": [metas[i]["ref"]
                                              for i in failed]},
                                 nonce_cache=nonces)
            except OSError:
                fresh = {}
            verdicts = fresh.get("deps") or []
            if fresh.get("ok") and len(verdicts) == len(failed):
                for i, verdict in zip(failed, verdicts):
                    if not verdict.get("ok"):
                        # per-dep refusal (cross-tenant, no live copy):
                        # final for THIS dep, the others keep their wins
                        errors[i] = KeyError(str(verdict.get("error")))
                        continue
                    ok, out = fetch_dep(verdict["dep"])
                    if ok:
                        values[i] = out
                        del errors[i]
                    else:
                        errors[i] = out
        if errors:
            i = min(errors)
            err = errors[i]
            if isinstance(err, Exception):
                raise err
            raise KeyError(
                f"dependency {metas[i]['ref']} has no reachable source")
        return values

    def result_meta_cb(tid: str, ref: ObjectRef):
        """Apply the head's verdict on a piggybacked result_meta ack:
        admission refusal deletes the local blob, over-quota spills it,
        and a handler-level refusal degrades to a queued error report
        (the same way a lost relay reply would have)."""
        def apply(reply: Optional[Dict[str, Any]]):
            if not isinstance(reply, dict) or not reply.get("ok", False):
                err = (reply or {}).get("error", "no reply")
                pending_ops.append((
                    {"op": "error", "task": tid, "worker": wid,
                     "err": f"result delivery failed: {err}"}, None))
                return
            if not reply.get("stored", False):
                local.delete(ref)      # admission failed head-side
                tenants.pop(ref.id, None)
            elif reply.get("spill"):
                local.spill(ref)   # over byte quota: degrade self to disk
        return apply

    def run_task(tid: str, got: Dict[str, Any]):
        try:
            if "deps" in got:          # p2p: control payload + dep metadata
                fn, args, kwargs = _dec(got["payload"])
                deps = resolve_deps(got["deps"], tid)
            else:                      # relay: dep values ride the payload
                fn, args, kwargs, deps = _dec(got["payload"])
            out = fn(*args, *deps, **kwargs)
        except Exception as e:  # noqa: BLE001 -- queued, not sent: the
            # report rides the next poll's batch frame, and an unreachable
            # head can no longer kill the worker mid-report
            pending_ops.append((
                {"op": "error", "task": tid, "worker": wid,
                 "err": f"{type(e).__name__}: {e}"}, None))
            return
        if "deps" in got and blob_srv is not None:
            # result stays local: the head records metadata only, and the
            # registration itself is QUEUED -- it piggybacks on the next
            # poll as a batch sub-op instead of costing a round trip
            ref = ObjectRef(f"obj-{tid}")
            blob = pickle.dumps(out, protocol=pickle.HIGHEST_PROTOCOL)
            local.put_blob(ref, blob)
            tenants[ref.id] = got.get("tenant", "default")
            pending_ops.append((
                {"op": "result_meta", "task": tid, "worker": wid,
                 "size": len(blob)}, result_meta_cb(tid, ref)))
            return
        try:
            _request(ep.host, ep.port, token,
                     {"op": "result", "task": tid, "worker": wid,
                      "payload": _enc(out)}, nonce_cache=nonces)
        except Exception as e:  # noqa: BLE001 -- reporting must never kill
            # the worker: a truncated reply (JSONDecodeError), a stale
            # envelope (SecurityError) or an unreachable head all degrade
            # to a queued error report + requeue-via-heartbeat, and our
            # local blobs survive for the leave/drain handshake
            pending_ops.append((
                {"op": "error", "task": tid, "worker": wid,
                 "err": f"result delivery failed: "
                        f"{type(e).__name__}: {e}"}, None))
            return

    actors: Dict[str, Any] = {}    # hosted service actors (id -> instance)
    create_errors: Dict[str, str] = {}   # actor id -> why its create failed

    def handle_actor_op(d: Dict[str, Any]):
        """Execute one head-queued actor lifecycle directive. Every
        outcome is acked through `pending_ops` (the next poll's batch
        frame): a create that cannot be satisfied acks an immediate
        exit, carrying the factory's exception text, so the head
        releases the lifetime resource hold instead of leaking it
        against a phantom replica, and callers learn why."""
        aop = d.get("op")
        aid = str(d.get("actor"))
        if aop == "actor_create":
            factory = (actor_factories or {}).get(str(d.get("factory")))
            try:
                if factory is None:
                    raise KeyError(f"no actor factory {d.get('factory')!r}")
                actors[aid] = factory(**(d.get("kwargs") or {}))
            except Exception as e:  # noqa: BLE001 -- unknown factory, bad
                # kwargs, a replica that cannot load: unhost immediately,
                # the head-side registration must not outlive the failure
                create_errors[aid] = f"{type(e).__name__}: {e}"
                pending_ops.append((
                    {"op": "actor_exit", "worker": wid, "actor": aid,
                     "error": create_errors[aid]}, None))
            return
        if aop == "actor_call":
            call_id = str(d.get("call"))
            inst = actors.get(aid)
            if inst is None:
                why = create_errors.get(aid)
                err = f"actor {aid!r} is not hosted here"
                if why:
                    err += f": create failed: {why}"
                pending_ops.append((
                    {"op": "actor_result", "worker": wid, "actor": aid,
                     "call": call_id, "error": err}, None))
                return
            try:
                payload = (_dec(d["payload"])
                           if d.get("payload") is not None else None)
                kind = (payload.get("kind") if isinstance(payload, dict)
                        else None)
                with SPANS.span("actor.handle", call=call_id, kind=kind):
                    value = inst.handle(payload)
                pending_ops.append((
                    {"op": "actor_result", "worker": wid, "actor": aid,
                     "call": call_id, "value": _enc(value)}, None))
            except Exception as e:  # noqa: BLE001 -- per-call verdict
                pending_ops.append((
                    {"op": "actor_result", "worker": wid, "actor": aid,
                     "call": call_id,
                     "error": f"{type(e).__name__}: {e}"}, None))
            return
        if aop == "actor_exit":
            inst = actors.pop(aid, None)
            if inst is not None and hasattr(inst, "drain"):
                try:
                    inst.drain()       # finish in-flight decodes first
                except Exception:  # noqa: BLE001 -- exit anyway
                    pass
            pending_ops.append((
                {"op": "actor_exit", "worker": wid, "actor": aid}, None))

    def safe_to_leave() -> bool:
        """Idle-exit handshake: replicate solely-held hot blobs to the
        head's push assignments until the head confirms the exit."""
        failures = 0
        for _ in range(50):            # bounded: a wedged peer set cannot
            try:                       # spin the worker forever
                left = _request(ep.host, ep.port, token,
                                {"op": "leave", "worker": wid},
                                nonce_cache=nonces)
            except Exception:  # noqa: BLE001
                # one refused connect must NOT bypass the sole-copy
                # handshake -- only a persistently unreachable head
                # (cluster gone) releases the worker
                failures += 1
                if failures >= 5:
                    return True
                time.sleep(0.2)
                continue
            failures = 0
            if left.get("exit", True):
                return True
            moves = left.get("replicate", [])
            if not moves:
                return False           # busy again: keep serving
            for mv in moves:
                ref = ObjectRef(mv["ref"])
                try:
                    blob = local.export_blob(ref)
                    transport = TCPTransport(
                        lambda _n, _ep=(mv["host"], int(mv["port"])): _ep,
                        token, wid)
                    transport.push(mv["node"], ref, blob,
                                   TransferTicket.from_wire(mv["ticket"]))
                    _request(ep.host, ep.port, token,
                             {"op": "pushed", "worker": wid,
                              "object": ref.id, "node": mv["node"]},
                             nonce_cache=nonces)
                except Exception:  # noqa: BLE001 -- re-planned next round
                    pass
            time.sleep(0.02)
        return False

    try:
        idle_since = time.monotonic()
        poll_failures = 0
        polls_since_metrics = 0
        while True:
            if time.monotonic() - idle_since >= max_idle_s:
                if actors:
                    # hosting a live replica: excluded from the idle-exit
                    # clock entirely -- a request-burst gap longer than
                    # max_idle_s must not trigger the leave handshake
                    idle_since = time.monotonic()
                elif safe_to_leave():
                    flush_metrics()
                    return
                else:
                    idle_since = time.monotonic()  # still needed: serve on
            # spill-tier counters accrue on the node store, drain-push
            # counters on wstats, the rest on the blob server; all ride
            # the same delta frame, with the poll-latency histogram's
            # sparse bucket deltas alongside
            deltas = compute_deltas()
            hist_delta = poll_hist.to_delta(poll_hist_base)
            sent = list(pending_ops)
            # telemetry cadence: deltas keep accruing worker-side and
            # ride every `metrics_every`-th poll -- the frames in
            # between stay exactly as small as an unmonitored worker's
            flush_due = (polls_since_metrics + 1 >= max(metrics_every, 1)
                         and bool(deltas or hist_delta["count"]))
            if sent or flush_due:
                # piggyback everything queued since the last poll on ONE
                # batch frame, the poll itself riding last
                ops = [o for o, _ in sent]
                if flush_due:
                    sub: Dict[str, Any] = {"op": "metric_deltas",
                                           "worker": wid, "deltas": deltas}
                    if hist_delta["count"]:
                        sub["hists"] = {
                            "syndeo_worker_poll_seconds": hist_delta}
                    ops.append(sub)
                ops.append({"op": "poll", "worker": wid})
                req: Dict[str, Any] = {"op": "batch", "worker": wid,
                                       "ops": ops}
            else:
                req = {"op": "poll", "worker": wid}
            try:
                poll_t0 = time.monotonic()
                got = _request(ep.host, ep.port, token, req,
                               nonce_cache=nonces)
                # observed AFTER the frame was built: this round trip's
                # latency rides the NEXT frame (or the exit flush); the
                # time the head held an empty poll is not latency
                rtt = time.monotonic() - poll_t0
                polled = ((got.get("replies") or [{}])[-1]
                          if sent or flush_due else got)
                poll_hist.observe(
                    max(0.0, rtt - float(polled.get("waited") or 0.0)))
            except OSError:
                # same tolerance as the leave handshake: one refused
                # connect (listen-backlog burst, transient timeout) must
                # not kill a worker that may hold sole copies -- only a
                # persistently unreachable head means the cluster is over.
                # Queued acks stay queued (and deltas un-advanced): they
                # replay on the next attempt.
                poll_failures += 1
                if poll_failures >= 5:
                    return
                time.sleep(0.2)
                continue
            poll_failures = 0
            polls_since_metrics += 1
            if sent or flush_due:
                replies = got.get("replies") or []
                del pending_ops[:len(sent)]
                if flush_due:
                    for k in metric_base:
                        metric_base[k] += deltas.get(k, 0)
                    poll_hist_base.apply_delta(hist_delta)
                    polls_since_metrics = 0
                update_truth()
                for (_op, cb), reply in zip(sent, replies[:len(sent)]):
                    if cb is not None:
                        cb(reply)      # may queue follow-up error reports
                got = replies[-1] if replies else {}
            if got.get("migrations"):
                # drain-move directives ride the poll reply: push the
                # blobs peer to peer before anything else -- the drain
                # cannot finish until these land (or fail and re-plan)
                run_migrations(got["migrations"])
            for directive in got.get("actor_ops") or []:
                handle_actor_op(directive)
            tid = got.get("task")
            if tid is None:
                if got.get("draining"):
                    # exit only when the head confirms the drain finished --
                    # a cancelled drain (backlog returned) keeps us serving
                    try:
                        status = _request(ep.host, ep.port, token,
                                          {"op": "drain_status",
                                           "worker": wid},
                                          nonce_cache=nonces)
                    except OSError:
                        status = {}    # transient: re-ask on the next poll
                    if status.get("complete"):
                        # the drain handshake's last act: deltas accrued
                        # since the final poll (the drain pushes above,
                        # the last polls' latencies) must not die with us
                        flush_metrics()
                        return
                if not (pending_ops or got.get("waited") is not None):
                    # nothing queued and the head did not hold the poll:
                    # pause before the next one. Queued results (a
                    # replica's answer) ride the next frame at once.
                    time.sleep(0.05)
                continue
            run_task(tid, got)
            # the idle clock starts *after* completion: a long task's next
            # empty poll must not read as max_idle_s of idleness
            idle_since = time.monotonic()
    finally:
        update_truth()         # post-mortem ground truth for the checker
        if blob_srv is not None:
            blob_srv.shutdown()
        if own_spill is not None:
            shutil.rmtree(own_spill, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", choices=["head", "worker"], required=True)
    ap.add_argument("--rendezvous", required=True)
    ap.add_argument("--cluster-id", required=True)
    ap.add_argument("--worker-id", default="")
    ap.add_argument("--max-idle-s", type=float, default=30.0)
    ap.add_argument("--data-plane", choices=["p2p", "relay"], default="p2p")
    ap.add_argument("--blob-host", default="127.0.0.1",
                    help="address this worker's blob server advertises to "
                         "peers -- on multi-machine fabrics pass the node's "
                         "reachable IP (e.g. $(hostname -i))")
    args = ap.parse_args()
    if args.role == "worker":
        run_worker(args.rendezvous, args.cluster_id, args.worker_id,
                   args.max_idle_s, data_plane=args.data_plane,
                   blob_host=args.blob_host)
    else:
        rdv = FileRendezvous(args.rendezvous)
        cluster = SyndeoCluster(rendezvous=rdv)
        cluster.cluster_id = args.cluster_id
        server = HeadServer(cluster, data_plane=args.data_plane)
        server.attach()
        print(f"head up on port {server.port}", flush=True)
        try:
            while True:
                time.sleep(1.0)
                cluster.health_check()
        except KeyboardInterrupt:
            server.shutdown()
            cluster.shutdown()


if __name__ == "__main__":
    main()
