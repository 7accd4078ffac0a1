"""Plain reference of the served OLTP path: OCC, Algorithm 1, the log, recovery.

Imports nothing of the program.  It is given what the clients asked for and
what each executor call was handed (the recorded cuts), and the raw bytes the
log devices hold after the crash.  It then works out, in straightforward
Python, what the paper's rules say must have happened, and counts every
place where the program's answers disagree:

* **outcomes** — within one cut a transaction wins iff no earlier
  transaction of the cut writes a key it touches and every SSN it observed is
  still current (first-come-wins OCC, §4.4);
* **reads** — every value a read-modify-write read is the value the
  reference holds for that key at the SSN it observed: the loaded row (SSN
  0, from the reference's own load of the seed) or a committed write;
* **SSNs** — ``ssn = max(max tuple SSN over RS ∪ WS, buffer SSN) + 1``
  per buffer in reservation order (Algorithm 1, §4.2); heartbeat records
  (tid 0, no writes) raise a buffer's SSN to the value they carry;
* **log bytes** — every frame on a device is ``u32 len | u32 crc32 |
  u64 ssn | u64 tid | u8 flags | u32 n | (u32 klen, key, u32 vlen, value)*``
  and the frames of a buffer appear in reservation order, the acknowledged
  ones all durable;
* **recovery** — ``RSNe`` is the least over devices of the newest durable
  frame's SSN; a record with reads replays only at or below ``RSNe``, a
  write-only record always; the image is last-writer-wins by SSN (§5);
* **acknowledgements** — no client is answered before the device write
  that made its record durable returned, and every acknowledged write is in
  the recovered image (or overwritten there by a higher SSN).

Each count has the limit 0.
"""

from __future__ import annotations

import bisect
import glob
import os
import struct
import zlib
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

HDR = struct.Struct("<II")        # payload length, crc32 of payload
FIXED = struct.Struct("<QQBI")    # ssn, tid, flags, number of writes
U32 = struct.Struct("<I")
HAS_READS = 0x01
# tids are drawn per worker as worker + 1 + k * stride (tid 0: heartbeats)
TID_STRIDE = 1024


def encode(ssn: int, tid: int, flags: int,
           writes: Iterable[Tuple[bytes, bytes]]) -> bytes:
    parts = []
    n = 0
    for kb, v in writes:
        parts += [U32.pack(len(kb)), kb, U32.pack(len(v)), v]
        n += 1
    payload = FIXED.pack(ssn, tid, flags, n) + b"".join(parts)
    return HDR.pack(len(payload), zlib.crc32(payload)) + payload


@dataclass
class Frame:
    start: int
    end: int
    ssn: int
    tid: int
    flags: int
    writes: List[Tuple[bytes, bytes]]

    @property
    def heartbeat(self) -> bool:
        return self.tid == 0 and not self.writes


def decode(buf: bytes) -> Tuple[List[Frame], int]:
    """Every whole, crc-valid frame from the start of ``buf``, stopping at
    the first torn or corrupt one; returns the frames and the bytes used."""
    frames: List[Frame] = []
    off, n = 0, len(buf)
    while off + HDR.size <= n:
        length, crc = HDR.unpack_from(buf, off)
        end = off + HDR.size + length
        if end > n or length < FIXED.size:
            break
        payload = buf[off + HDR.size:end]
        if zlib.crc32(payload) != crc:
            break
        ssn, tid, flags, nw = FIXED.unpack_from(payload, 0)
        p, writes, ok = FIXED.size, [], True
        for _ in range(nw):
            if p + 4 > length:
                ok = False
                break
            (kl,) = U32.unpack_from(payload, p)
            kb = payload[p + 4:p + 4 + kl]
            p += 4 + kl
            if p + 4 > length:
                ok = False
                break
            (vl,) = U32.unpack_from(payload, p)
            v = payload[p + 4:p + 4 + vl]
            p += 4 + vl
            if p > length:
                ok = False
                break
            writes.append((kb, v))
        if not ok:
            break
        frames.append(Frame(off, end, ssn, tid, flags, writes))
        off = end
    return frames, off


def device_bytes(directory: str, index: int, prefix: str = "log") -> bytes:
    """A device's log as it lies on disk: its sealed segment files in offset
    order (``<prefix>_<i>.bin.seg-<offset>``, zero-padded), then the tail."""
    tail = os.path.join(directory, f"{prefix}_{index}.bin")
    sealed = sorted(glob.glob(tail + ".seg-*"))
    out = []
    for p in sealed + ([tail] if os.path.exists(tail) else []):
        with open(p, "rb") as f:
            out.append(f.read())
    return b"".join(out)


@dataclass
class Spec:
    """One attempt of a transaction as its client built it."""

    reads: Sequence[str] = ()
    writes: Sequence[Tuple[str, bytes]] = ()
    observed: Optional[Sequence[int]] = None   # SSNs seen, aligned with reads
    values: Optional[Sequence[bytes]] = None   # values seen, aligned with reads


@dataclass
class Cut:
    """One executor call as the scheduler made it, and what it answered."""

    tags: Sequence[int]        # the attempts, by the tag their client gave
    specs: Sequence[Spec]
    workers: Sequence[int]
    committed: Sequence[Tuple[int, int, int]]   # (spec index, ssn, tid)
    aborted: Sequence[int]


@dataclass
class Verdict:
    checks: Dict[str, int] = field(default_factory=dict)
    # tag -> (committed?, ssn, where) for each attempt; where is (device, end
    # offset) of its frame, (-1, 0) for a read-only winner, None for a
    # record the crash lost
    attempts: Dict[int, Tuple[bool, int, Optional[Tuple[int, int]]]] = field(
        default_factory=dict)
    image: Dict[bytes, Tuple[bytes, int]] = field(default_factory=dict)
    rsne: int = 0
    n_records: int = 0
    log_bytes: int = 0       # bytes of whole frames on the devices
    user_bytes: int = 0      # value bytes those frames carry

    def bump(self, name: str, n: int = 1) -> None:
        self.checks[name] = self.checks.get(name, 0) + n


CHECK_NAMES = (
    "exec_errors", "outcome_mismatch", "read_value_mismatch",
    "ssn_mismatch", "log_frame_mismatch",
    "log_unexpected_bytes", "stuck_tickets", "committed_not_acked",
    "acked_not_durable", "acked_before_durable", "acked_missing", "image_mismatch", "rsne_mismatch",
    "consistency_violations",
)


def replay_cuts(cuts: Sequence[Cut], n_buffers: int,
                streams: Sequence[bytes], torn: bytes,
                loaded: Optional[Dict[str, bytes]] = None) -> Verdict:
    """Check the executor's answers and the device bytes against the rules.

    ``streams[b]`` is device ``b``'s raw log; ``torn`` the partial frame the
    crash left at the end of device 0 (the only bytes allowed past the last
    whole frame); ``loaded`` the loaded value of every key a spec with
    ``values`` read."""
    v = Verdict(checks={k: 0 for k in CHECK_NAMES})
    frames = []
    for b, s in enumerate(streams):
        fr, used = decode(s)
        frames.append(fr)
        rest = s[used:]
        if rest != (torn if b == 0 else b""):
            v.bump("log_unexpected_bytes", max(1, len(rest)))
        v.log_bytes += used
    pos = [0] * n_buffers
    buf_ssn = [0] * n_buffers
    key_ssn: Dict[str, int] = {}
    # (key, ssn) -> value of every committed write; SSN 0 is the load
    versions: Dict[Tuple[str, int], bytes] = {}
    loaded = loaded or {}
    next_tid: Dict[int, int] = {}
    exact = True   # false once a record was lost in the crash: from then on
    #                SSNs are checked as lower bounds (a lost heartbeat may
    #                have raised them)

    def heartbeats(b: int) -> None:
        fr = frames[b]
        while pos[b] < len(fr) and fr[pos[b]].heartbeat:
            if fr[pos[b]].ssn < buf_ssn[b]:
                v.bump("ssn_mismatch")
            buf_ssn[b] = fr[pos[b]].ssn
            pos[b] += 1

    for cut in cuts:
        specs, n = cut.specs, len(cut.specs)
        first: Dict[str, int] = {}
        for i, s in enumerate(specs):
            for k, _ in s.writes:
                first.setdefault(k, i)
        winners, base = [], {}
        for i, s in enumerate(specs):
            if s.values is not None:
                for k, o, val in zip(s.reads, s.observed, s.values):
                    want = versions.get((k, o)) if o else loaded.get(k)
                    if val != want:
                        v.bump("read_value_mismatch")
            keys = list(s.reads) + [k for k, _ in s.writes]
            ok = all(first.get(k, n) >= i for k in keys)
            if ok and s.observed is not None:
                ok = all(key_ssn.get(k, 0) == int(o)
                         for k, o in zip(s.reads, s.observed))
            if ok:
                winners.append(i)
                base[i] = max((key_ssn.get(k, 0) for k in keys), default=0)
        got = {i: (ssn, tid) for i, ssn, tid in cut.committed}
        want = set(winners)
        v.bump("outcome_mismatch", len(want ^ set(got))
               + len(set(cut.aborted) ^ (set(range(n)) - want)))
        tid = {}
        for i in winners:
            w = cut.workers[i]
            tid[i] = next_tid.get(w, w + 1)
            next_tid[w] = tid[i] + TID_STRIDE
        ssn = {i: base[i] for i in winners}          # read-only: ssn = base
        for b in range(n_buffers):
            for i in winners:
                s = specs[i]
                if not s.writes or cut.workers[i] % n_buffers != b:
                    continue
                heartbeats(b)
                want_ssn = max(base[i], buf_ssn[b]) + 1
                flags = HAS_READS if s.reads else 0
                writes = [(k.encode(), val) for k, val in s.writes]
                p_ssn, p_tid = got.get(i, (None, None))
                if pos[b] < len(frames[b]):
                    fr = frames[b][pos[b]]
                    pos[b] += 1
                    where = (b, fr.end)
                    if exact:
                        same = streams[b][fr.start:fr.end] == encode(
                            want_ssn, tid[i], flags, writes)
                    else:
                        same = ((fr.tid, fr.flags, fr.writes)
                                == (tid[i], flags, writes))
                        if fr.ssn < want_ssn:
                            v.bump("ssn_mismatch")
                        want_ssn = fr.ssn
                    if not same:
                        v.bump("log_frame_mismatch")
                    if p_ssn is not None and p_ssn != want_ssn:
                        v.bump("ssn_mismatch")
                else:
                    where = None
                    exact = False
                    if p_ssn is not None:
                        if p_ssn < want_ssn:
                            v.bump("ssn_mismatch")
                        want_ssn = max(want_ssn, p_ssn)
                if p_tid is not None and p_tid != tid[i]:
                    v.bump("ssn_mismatch")
                buf_ssn[b] = want_ssn
                ssn[i] = want_ssn
                v.attempts[cut.tags[i]] = (True, want_ssn, where)
        for i in winners:
            for k, val in specs[i].writes:
                key_ssn[k] = ssn[i]
                versions[(k, ssn[i])] = val
            if not specs[i].writes:
                v.attempts[cut.tags[i]] = (True, ssn[i], (-1, 0))
        for i in set(range(n)) - want:
            v.attempts[cut.tags[i]] = (False, -1, None)
    for b in range(n_buffers):
        heartbeats(b)
        if pos[b] != len(frames[b]):
            v.bump("log_frame_mismatch", len(frames[b]) - pos[b])
    v.image, v.rsne, v.n_records = recover(frames)
    v.user_bytes = sum(len(val) for fr in frames for f in fr
                       for _, val in f.writes)
    return v


def recover(frames: Sequence[Sequence[Frame]]
            ) -> Tuple[Dict[bytes, Tuple[bytes, int]], int, int]:
    """§5 log recovery over decoded device logs: ``(image, RSNe, records)``."""
    rsne = min((fr[-1].ssn if fr else 0) for fr in frames) if frames else 0
    image: Dict[bytes, Tuple[bytes, int]] = {}
    n = 0
    for fr in frames:
        for f in fr:
            if f.heartbeat:
                continue
            n += 1
            if f.flags & HAS_READS and f.ssn > rsne:
                continue
            for kb, val in f.writes:
                cur = image.get(kb)
                if cur is None or f.ssn > cur[1]:
                    image[kb] = (val, f.ssn)
    return image, rsne, n


def check_tickets(v: Verdict, answers: Dict[str, np.ndarray], spec_of,
                  program_image: Dict[bytes, Tuple[bytes, int]],
                  program_rsne: int,
                  writes: Sequence[Sequence[Tuple[float, int]]]) -> None:
    """Hold the clients' answers and the program's recovered image to the
    reference.  ``answers`` holds per ticket its answer ``code`` (0 acked,
    1 aborted, 2 rejected, 3 never answered), the ``t_ack`` at which it came,
    the ``ssn`` it gave and the ``tag`` of the ticket's last attempt, for the
    window's tickets, all settled before the crash, and those of the burst
    answered before it; ``spec_of(tag)`` is that attempt.  ``writes[b]``
    lists device ``b``'s writes as ``(time it returned, bytes on the device
    after it)``, on the clock of ``t_ack``."""
    code, t_ack = answers["code"], answers["t_ack"]
    ssns, tags = answers["ssn"], answers["tag"]
    ends = [[e for _, e in w] for w in writes]
    v.bump("stuck_tickets", int((code == 3).sum()))
    for j in np.flatnonzero(code == 1):
        if v.attempts.get(int(tags[j]), (False,))[0]:
            v.bump("committed_not_acked")
    for j in np.flatnonzero(code == 0):
        tag, t_ssn = int(tags[j]), int(ssns[j])
        ok, ssn, where = v.attempts.get(tag, (False, -1, None))
        if not ok or t_ssn != ssn:
            v.bump("ssn_mismatch")
        if where is None:
            v.bump("acked_not_durable")
        elif where[0] >= 0:
            k = bisect.bisect_left(ends[where[0]], where[1])
            if k == len(ends[where[0]]) or t_ack[j] < writes[where[0]][k][0]:
                v.bump("acked_before_durable")
        for k, val in spec_of(tag).writes:
            got = v.image.get(k.encode())
            if got is None or not (got[1] > t_ssn or got == (val, t_ssn)):
                v.bump("acked_missing")
    if program_image != v.image:
        keys = set(program_image) | set(v.image)
        v.bump("image_mismatch", sum(program_image.get(k) != v.image.get(k)
                                     for k in keys))
    if program_rsne != v.rsne:
        v.bump("rsne_mismatch")
