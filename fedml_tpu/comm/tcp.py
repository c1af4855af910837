"""Native TCP comm backend (cross-silo / DCN role).

The reference fills this role with gRPC C-core (grpc_comm_manager.py:23):
each rank runs a server and sends JSON messages to ``ip_config[receiver]``.
Here the transport is the in-repo C++ ``msgnet`` library (length-prefixed
frames over cached TCP connections, event-driven condvar queue — see
fedml_tpu/native/msgnet.cpp) and the payload is the pickled ``Message``
param dict, the same wire content the reference's MPI backend ships
(mpi_send_thread.py:27 pickles whole dicts).

Unlike the reference's gRPC manager — which listens on 50000+rank but sends
to 8888+rank (grpc_comm_manager.py:59-63, a latent port mismatch; SURVEY.md
§2.1) — the ip table here is the single source of truth for both sides.

``read_ip_config`` parses the reference's ``grpc_ipconfig.csv`` format
(receiver_id,ip[,port]).
"""

from __future__ import annotations

import ctypes
import csv
from typing import Dict, List, Optional, Tuple

from fedml_tpu.comm.base import BaseCommunicationManager, Observer
from fedml_tpu.comm.message import Message
from fedml_tpu.comm.resilience import RetryPolicy
from fedml_tpu.comm.wire import (ByteLedger, WIRE_FORMATS,
                                 deserialize_message, serialize_message)

DEFAULT_BASE_PORT = 50000


def read_ip_config(path: str, base_port: int = DEFAULT_BASE_PORT) -> Dict[int, Tuple[str, int]]:
    """csv ``receiver_id,ip[,port]`` → {rank: (host, port)}; port defaults
    to base_port+rank (utils/ip_config_utils.py:4 reads id→ip only)."""
    out: Dict[int, Tuple[str, int]] = {}
    with open(path) as f:
        for row in csv.reader(f):
            if not row or row[0].strip().startswith("#"):
                continue
            if row[0].strip().lower() in ("receiver_id", "rank"):
                continue  # header
            rank = int(row[0])
            host = row[1].strip()
            port = int(row[2]) if len(row) > 2 else base_port + rank
            out[rank] = (host, port)
    return out


class TcpCommManager(BaseCommunicationManager):
    """One instance per rank.

    ``ip_config``: {rank: (host, port)}. The server binds ``port`` for this
    rank (0 = ephemeral, then ``port`` property reports it — handy in
    tests).
    """

    def __init__(self, ip_config: Dict[int, Tuple[str, int]], rank: int,
                 backlog: int = 128, serializer: str = "pickle",
                 retry_first: Optional[RetryPolicy] = None,
                 retry: Optional[RetryPolicy] = None):
        """``serializer``: 'pickle' or 'json' — see
        :mod:`fedml_tpu.comm.wire` for the trust trade-off.

        ``retry_first`` / ``retry``: the shared RetryPolicy pair — used
        until a peer is first reached / afterwards (comm.resilience)."""
        from fedml_tpu.native import load_msgnet

        if serializer not in WIRE_FORMATS:
            raise ValueError(f"unknown serializer {serializer!r}")
        self._serializer = serializer
        self._retry_first = retry_first or RetryPolicy.first_contact(seed=rank)
        self._retry = retry or RetryPolicy.established(seed=rank)
        self._lib = load_msgnet()
        self.rank = rank
        # Shared BY REFERENCE: with ephemeral ports (port 0) each rank
        # writes its resolved port back so peers constructed from the same
        # table see it (single-host setups construct all managers
        # sequentially before any send).
        self.ip_config = ip_config
        port = self.ip_config[rank][1]
        self._server = self._lib.mn_server_create(port, backlog)
        if self._server < 0:
            raise OSError(f"msgnet: cannot bind port {port} for rank {rank}")
        real_port = self._lib.mn_server_port(self._server)
        self.ip_config[rank] = (self.ip_config[rank][0], real_port)
        self._sender = self._lib.mn_sender_create()
        self.bytes_ledger = ByteLedger()
        self._observers: List[Observer] = []
        self._running = False
        self._stop_requested = False
        self._contacted: set = set()  # peers reached at least once

    @property
    def port(self) -> int:
        return self.ip_config[self.rank][1]

    @property
    def retry_count(self) -> int:
        return self._retry_first.retries + self._retry.retries

    def _send_once(self, receiver: int, host: str, port: int,
                   blob: bytes) -> None:
        """One transport attempt — the unit the RetryPolicy wraps.
        bytes → const uint8* zero-copy (argtype c_char_p)."""
        rc = self._lib.mn_send(self._sender, host.encode(), port, blob,
                               len(blob))
        if rc != 0:
            raise ConnectionError(
                f"msgnet: send from rank {self.rank} to {receiver} "
                f"({host}:{port}) failed (rc={rc})")
        self._contacted.add(receiver)

    # -- BaseCommunicationManager ------------------------------------------
    def send_message(self, msg: Message) -> None:
        """Send under the shared RetryPolicy: generous first-contact
        retries (cross-silo processes start in any order, so the first
        sends may race the receiver's bind — the reference's MPI launcher
        sidesteps this because mpirun barrier-starts all ranks); once a
        peer has been contacted, one quick re-attempt (the C layer
        reconnects), then raise — a crashed silo must surface in ~0 s,
        not after a retry window per message."""
        receiver = int(msg.get_receiver_id())
        blob = serialize_message(msg, self._serializer)
        policy = (self._retry if receiver in self._contacted
                  else self._retry_first)
        # ip_config is re-read per attempt: a restarted peer may have
        # rebound an ephemeral port into the shared table mid-retry.
        policy.run(
            lambda: self._send_once(receiver, *self.ip_config[receiver],
                                    blob),
            retriable=lambda e: isinstance(e, (ConnectionError, OSError)),
            describe=f"msgnet send rank {self.rank} -> {receiver}")
        self.bytes_ledger.count_tx(receiver, len(blob))

    def add_observer(self, observer: Observer) -> None:
        self._observers.append(observer)

    def remove_observer(self, observer: Observer) -> None:
        self._observers.remove(observer)

    def handle_receive_message(self) -> None:
        """Blocking receive loop; returns after ``stop_receive_message`` —
        including a stop that ran BEFORE this loop started (a server
        restored at the terminal round can finish inside send_init_msg;
        re-arming unconditionally here would then spin forever on the
        already-stopped native server)."""
        self._running = not self._stop_requested
        out_len = ctypes.c_uint64()
        while self._running:
            ptr = self._lib.mn_server_recv(self._server, 200, ctypes.byref(out_len))
            if not ptr:
                continue  # timeout tick: re-check _running
            try:
                blob = ctypes.string_at(ptr, out_len.value)
            finally:
                self._lib.mn_free(ptr)
            msg = deserialize_message(blob, self._serializer)
            self.bytes_ledger.count_rx(int(msg.get_sender_id()), len(blob))
            for obs in list(self._observers):
                obs.receive_message(msg.get_type(), msg)

    def stop_receive_message(self) -> None:
        self._stop_requested = True  # latched: stop-before-start must hold
        self._running = False

    def close(self) -> None:
        self.stop_receive_message()
        self._lib.mn_server_stop(self._server)
        self._lib.mn_sender_destroy(self._sender)
