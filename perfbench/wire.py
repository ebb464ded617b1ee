"""The host side of the sidecar protocol, as the benchmark speaks it.

Framing is proto/sidecar.proto's: a 4-byte big-endian length, then one
``Envelope``.  The message classes are the ones generated from that file
(``kubernetes_tpu.sidecar.sidecar_pb2``); nothing else of the program's
client code is used.  Why the yardstick owns its side of the wire:

  * a deployment's client is the Go plugin (go/tpubatchscore); the
    program's ``SidecarClient`` and ``DecisionCache`` are Python stand-ins
    for it that any later PR may edit, and a number that moved because
    they did would say nothing of the sidecar;
  * ``SidecarClient.schedule`` serialises each pod object inside the call
    (``serialize.to_json``), so a window driven through it times the
    client's own JSON: PR 22 read 1,006 pods/s through it on the commit
    that reads 1,480 here, where every frame is bytes before the window;
  * ``DecisionCache.drain`` cannot be exact: its reader thread may hold
    bytes it has not yet put into the buffer, so a caller waits
    (``integrated.py``: 50 ms whenever a batch pushed nothing) or misses
    decisions that have arrived.  ``PushMap.drain`` reads the socket under
    the reader's lock and so takes everything written before the answer.

Two pieces, one per connection of the Go plugin:

  Conn        request/response calls (Schedule, adds, health, metrics,
              flight, dump)
  PushMap     the subscribed connection: a reader thread that only moves
              bytes off the socket, and the plugin-local decision map the
              consumer thread applies frames into, in stream order

The plugin's hint flusher shares the request connection (its client's
mutex), so hint frames are ordinary calls on ``Conn``.
"""

from __future__ import annotations

import json
import select
import socket
import struct
import threading

_LEN = struct.Struct(">I")


def _pb():
    from kubernetes_tpu.sidecar import sidecar_pb2

    return sidecar_pb2


def frame(env) -> bytes:
    payload = env.SerializeToString()
    return _LEN.pack(len(payload)) + payload


def schedule_frame(pod_json: bytes, seq: int = 0) -> bytes:
    """One-pod ``Schedule`` request (drain=False), ready to send."""
    env = _pb().Envelope(seq=seq)
    env.schedule.drain = False
    env.schedule.pod_json.append(pod_json)
    return frame(env)


def add_frame(kind: str, object_json: bytes, seq: int = 0) -> bytes:
    env = _pb().Envelope(seq=seq)
    env.add.kind = kind
    env.add.object_json = object_json
    return frame(env)


def pending_pods_frame(pod_jsons, seq: int = 0) -> bytes:
    """One coalesced ``PendingPods`` hint frame: a JSON array of pods."""
    return add_frame("PendingPods", b"[" + b",".join(pod_jsons) + b"]", seq)


def _read_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("sidecar closed the connection")
        buf += chunk
    return bytes(buf)


def read_envelope(sock: socket.socket):
    (n,) = _LEN.unpack(_read_exact(sock, 4))
    env = _pb().Envelope()
    env.ParseFromString(_read_exact(sock, n))
    return env


class Conn:
    """One request/response connection.  Frames built ahead of time carry
    seq 0, which the server echoes; the server answers in the order it was
    asked.  Calls are one at a time, but for ``post``: a frame written now
    whose acknowledgement is read later, by the next posts as they write or
    by the next call before it sends."""

    def __init__(self, path: str, timeout_s: float = 300.0):
        self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.sock.settimeout(timeout_s)
        self.sock.connect(path)
        self._owed = 0  # posted frames whose acknowledgement has not been read
        self._inbuf = bytearray()  # the part of an acknowledgement an earlier read left
        self._errors: list[str] = []

    def call_raw(self, data: bytes):
        if self._owed or self._errors:  # what was posted is settled first
            self.call_many(b"", 0)
        self.sock.sendall(data)
        env = read_envelope(self.sock)
        if env.response.error:
            raise RuntimeError(env.response.error)
        return env.response

    def _simple(self, field: str):
        env = _pb().Envelope()
        getattr(env, field).SetInParent()
        return self.call_raw(frame(env))

    def health(self) -> dict:
        return json.loads(self._simple("health").health_json)

    def metrics_text(self) -> str:
        return self._simple("metrics").metrics_text.decode()

    def dump(self) -> dict:
        return json.loads(self._simple("dump").dump_json)

    def flight(self, limit: int = 0) -> dict:
        env = _pb().Envelope()
        env.flight.SetInParent()
        if limit:
            env.flight.limit = limit
        return json.loads(self.call_raw(frame(env)).flight_json)

    def schedule_raw(self, data: bytes) -> str:
        """A prebuilt one-pod Schedule frame; the node name ("" = none)."""
        results = self.call_raw(data).results
        return results[0].node_name if results else ""

    def add_many(self, kind: str, object_jsons) -> None:
        """Pipelined adds (the informer's initial list)."""
        self.call_many(b"".join(add_frame(kind, j) for j in object_jsons), len(object_jsons))

    def _acks(self) -> int:
        """Reads what the socket holds, if anything (the caller has it
        non-blocking), and counts the whole acknowledgements in it; their
        errors are kept."""
        try:
            chunk = self.sock.recv(1 << 16)
        except BlockingIOError:
            return 0
        if not chunk:
            raise ConnectionError("sidecar closed the connection")
        buf = self._inbuf
        buf += chunk
        off = got = 0
        while len(buf) - off >= 4:
            (n,) = _LEN.unpack_from(buf, off)
            if len(buf) - off - 4 < n:
                break
            env = _pb().Envelope()
            env.ParseFromString(bytes(buf[off + 4: off + 4 + n]))
            if env.response.error:
                self._errors.append(env.response.error)
            got += 1
            off += 4 + n
        del buf[:off]
        return got

    def call_many(self, frames: bytes, want: int) -> None:
        """``want`` prebuilt AddObject frames, of any kinds, pipelined in
        the order given: write while draining acks, so neither side's
        socket buffer fills.  Returns once they and every frame posted
        before them are acknowledged."""
        data = memoryview(frames)
        want += self._owed
        self._owed = 0
        got = 0
        sock = self.sock
        sock.setblocking(False)
        try:
            while got < want:
                rl, wl, _ = select.select([sock], [sock] if data else [], [], 300.0)
                if not rl and not wl:
                    raise TimeoutError("sidecar stopped acknowledging adds")
                if wl:
                    try:
                        data = data[sock.send(data[: 1 << 20]):]
                    except BlockingIOError:
                        pass
                if rl:
                    got += self._acks()
        finally:
            sock.settimeout(300.0)
        if self._errors:
            errors, self._errors = self._errors, []
            raise RuntimeError(f"{len(errors)} adds failed ({want} were waited for); first: {errors[0]}")

    def post(self, data: bytes) -> None:
        """One prebuilt AddObject frame, written now and not waited for:
        its acknowledgement is read while later posts write, or by the
        next call on this connection before it sends (the server takes a
        connection's frames in order, so nothing overtakes it)."""
        self._owed += 1
        view = memoryview(data)
        sock = self.sock
        sock.setblocking(False)
        try:
            while True:
                try:
                    view = view[sock.send(view):]
                except BlockingIOError:
                    pass
                self._owed -= self._acks()
                if not view:
                    return
                rl, wl, _ = select.select([sock], [sock], [], 300.0)
                if not rl and not wl:
                    raise TimeoutError("sidecar stopped reading adds")
        finally:
            sock.settimeout(300.0)

    def close(self) -> None:
        self.sock.close()


class PushMap:
    """The plugin-local decision map (plugin.go's subscriber goroutine and
    the map PreFilter answers from).  Frames are applied by the consumer
    thread in stream order; ``order`` keeps every decided pod in the
    order the sidecar committed it, which the correctness check replays."""

    def __init__(self, path: str):
        self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.sock.connect(path)
        env = _pb().Envelope(seq=1)
        env.subscribe.SetInParent()
        self.sock.sendall(frame(env))
        ack = read_envelope(self.sock)
        if ack.response.error:
            raise RuntimeError(ack.response.error)
        self.map: dict[str, str] = {}
        self.order: list[tuple[str, str]] = []  # (uid, node) in commit order
        self.batches: list[int] = []  # decisions per push frame
        self.frames = 0
        self.invalidations = 0
        self._buf = bytearray()
        self._io = threading.Lock()
        self._closed = False
        self.sock.setblocking(False)
        self._reader = threading.Thread(target=self._read_loop, daemon=True)
        self._reader.start()

    def _pull(self) -> None:
        """Move every byte the socket holds into the buffer (caller holds
        the lock)."""
        while True:
            try:
                chunk = self.sock.recv(1 << 20)
            except BlockingIOError:
                return
            except OSError:
                chunk = b""
            if not chunk:
                self._closed = True
                return
            self._buf += chunk

    def _read_loop(self) -> None:
        # Only keeps the socket drained while the consumer sits in a wire
        # call: a big batch's pushes outgrow the socket buffers, and the
        # sidecar drops a subscriber whose socket stays full.
        while not self._closed:
            try:
                select.select([self.sock], [], [], 1.0)
            except (OSError, ValueError):
                return
            with self._io:
                self._pull()

    def drain(self) -> int:
        """Apply every frame written so far, in stream order.  The sidecar
        writes a batch's pushes before the response that follows them, so
        after a wire answer everything that batch decided is either in the
        buffer or in the socket, and this takes both: a pod that is still
        not in the map after it has not been decided."""
        with self._io:
            self._pull()
            frames = self._take_frames()
        for push in frames:
            self._apply(push)
        self.frames += len(frames)
        return len(frames)

    def _take_frames(self) -> list:
        buf = self._buf
        out = []
        off = 0
        while len(buf) - off >= 4:
            (n,) = _LEN.unpack_from(buf, off)
            if len(buf) - off - 4 < n:
                break
            env = _pb().Envelope()
            env.ParseFromString(bytes(buf[off + 4: off + 4 + n]))
            out.append(env.push)
            off += 4 + n
        if off:
            del buf[:off]
        return out

    def _apply(self, push) -> None:
        if push.invalidate_all:
            self.map.clear()
            self.invalidations += 1
        for uid in push.invalidate_uids:
            self.map.pop(uid, None)
            self.invalidations += 1
        n = 0
        for d in push.decisions:
            self.map[d.pod_uid] = d.node_name
            self.order.append((d.pod_uid, d.node_name))
            n += 1
        if n:
            self.batches.append(n)

    def pop(self, uid: str):
        return self.map.pop(uid, None)

    def note(self, uid: str, node: str) -> None:
        """A wire answer: the pod that started a batch rides the response,
        not the push stream; it takes its place in the commit order ahead
        of that batch's pushes, which are applied only after this."""
        self.order.append((uid, node))

    def close(self) -> None:
        self._closed = True
        self._reader.join(timeout=5.0)
        self.sock.close()
