"""Faults and controls planted under a run (`run.py --control NAME`).

A control run must end `correct: false`; the driver's own runs never plant
one.  They are planted where the timed path hands over what it produced —
the shard files an rpc has just written, the acknowledgement of a PUT — so
they stay valid whatever the program's internals become.

  rs-10-3          the control for the EC cells: the reference put in the
                   program's place with one guarantee of the configuration
                   broken — RS(10,3) passed off as RS(10,4): the last
                   parity shard holds no parity (zeros), so a fourth loss
                   is not survivable.  Rebuild cell: the last lost shard
                   is "rebuilt" as zeros.
  flip-shard-byte  an answer altered where it is produced: one byte of one
                   shard file the rpc wrote is flipped.
  lose-output      a step that returns its state unchanged: the rpc says it
                   is done and the shard files it should have written hold
                   nothing (truncated to no bytes).
  half-rows        half of the batch left out: the second half of the
                   stripe rows of every shard file the rpc wrote was never
                   computed (zeros).
  ack-not-stored   the control for the live cell: a PUT is acknowledged
                   and its bytes are not there (every 64th acknowledged
                   file is deleted behind the client's back).
  alter-put        an answer altered where it is produced: every 64th PUT
                   stores one flipped byte.
"""

from __future__ import annotations

import os

NAMES = ("rs-10-3", "flip-shard-byte", "lose-output", "half-rows",
         "ack-not-stored", "alter-put")


class Fault:
    def __init__(self, name: "str | None" = None, armed: bool = False):
        if name is not None and name not in NAMES:
            raise ValueError(f"unknown control {name!r}; one of {NAMES}")
        self.name = name
        self.armed = armed    # set when the window opens: warm-up runs sound
        self.fired = 0

    def ec_files(self, base: str, shard_ids: list) -> None:
        """Called after each timed EC rpc with the shard files it wrote."""
        if not shard_ids or not self.armed:
            return
        if self.name == "rs-10-3":
            path = f"{base}.ec{max(shard_ids):02d}"
            size = os.path.getsize(path)
            with open(path, "r+b") as f:
                f.seek(0)
                f.write(b"\0" * size)
            self.fired += 1
        elif self.name == "flip-shard-byte":
            # in the last stripe row, which every sample holds
            path = f"{base}.ec{shard_ids[len(shard_ids) // 2]:02d}"
            size = os.path.getsize(path)
            with open(path, "r+b") as f:
                f.seek(size - 4097)
                b = f.read(1)
                f.seek(size - 4097)
                f.write(bytes([b[0] ^ 0x40]))
            self.fired += 1

        elif self.name in ("lose-output", "half-rows"):
            for i in shard_ids:
                path = f"{base}.ec{i:02d}"
                size = os.path.getsize(path)
                keep = 0 if self.name == "lose-output" else size // 2
                with open(path, "r+b") as f:
                    f.truncate(keep)
                    if keep:
                        f.truncate(size)    # the rest reads as zeros
            self.fired += 1

    def put_body(self, n: int, body: bytes) -> bytes:
        """Called with the body of write number n before it is sent."""
        if self.armed and self.name == "alter-put" and n % 64 == 63:
            self.fired += 1
            return body[:100] + bytes([body[100] ^ 0x01]) + body[101:]
        return body

    def acked_put(self, conn, n: int, fid: str) -> None:
        """Called after write number n was acknowledged."""
        if self.armed and self.name == "ack-not-stored" and n % 64 == 63:
            conn.request("DELETE", "/" + fid)
            self.fired += 1
