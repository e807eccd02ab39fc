"""A generator that a later configuration might bring: `policy_flows`' churn
with the reply leg of the connections it has heard were committed.  It uses
both optional parts of the generator contract (manifest.py).

  observe    every lane of a forward leg that the program committed queues
             its connection's reply: from the address the program sent the
             packet to (`dnat_ip`/`dnat_port`: a Service's endpoint, or the
             server itself) to the client's address and port, the same
             protocol.  A reply to batch n's flows goes out in batch n + 2 or
             later (the contract's wait).  The queue keeps the newest
             `reply_backlog` replies, and a connection waits once.  A reply
             tuple heard for two frontends is never sent: the program's
             reply entry holds one of them, and which is its cache's
             business.
  lanes      `reply_lanes` lanes of every batch, every batch/reply_lanes-th
             one past a fresh lane, carry the oldest due replies; a lane
             left over keeps its hot flow, a forward leg.
  labels     `leg` (1 on a reply, 0 on a forward leg) and the frontend the
             lane's connection was sent to (`frontend_ip`, `frontend_port`;
             a forward leg's own destination).
  sampled    per step a quarter of the lanes at fresh lanes, a quarter at
             reply lanes, the rest at the others, drawn from the seed before
             any answer exists, as `policy_flows` draws its own.
"""
import collections
import os

import numpy as np

from manifest import load_module

_flows = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "policy_flows.py"))
class_shares = _flows.class_shares
REPLY = 1
# A queued reply is a row of seven: its five columns in this order, then the
# frontend its connection was sent to (ip, port).
_COLS = _flows._COLS


class Traffic(_flows.Traffic):
    def __init__(self, params: dict, world, seed: int, reference):
        super().__init__(params, world, seed, reference)
        n = self.reply_lanes = int(params["reply_lanes"])
        stride = self.batch // n
        if self.batch % n or (self.fresh_lanes and stride % self._stride):
            raise ValueError("reply_lanes has to divide batch, and each "
                             "reply lane fall one past a fresh lane")
        self.reply_at = np.arange(n) * stride + 1
        self._backlog = int(params["reply_backlog"])
        self._queue = collections.deque()  # (row of 7, first batch due)
        self._queued = set()  # reply tuples in the queue
        self._front = {}  # reply tuple -> the frontend it was heard for
        self._ambiguous = set()
        self._sent = collections.deque()  # (columns, reply lanes, batch no)
        self._built = 0
        k = int(params["sample_lanes_per_step"])
        srng = np.random.default_rng([seed, 3])
        others = np.setdiff1d(np.arange(self.batch),
                              np.concatenate([self.fresh_at, self.reply_at]))
        parts = [srng.choice(self.reply_at, size=(4096, k // 4))]
        if self.fresh_lanes:
            parts.append(srng.choice(self.fresh_at, size=(4096, k // 4)))
        parts.append(srng.choice(others, size=(4096, k - sum(
            p.shape[1] for p in parts))))
        self._sample = np.concatenate(parts, axis=1)

    def _sending(self, cols, at) -> None:
        self._sent.append((cols, at, self._built))
        self._built += 1

    def next_batch(self):
        """-> (columns, sampled lanes, fresh, lane labels)."""
        cols, lanes, fresh = super().next_batch()
        cols = {c: np.array(v) for c, v in cols.items()}
        rows = []
        while (self._queue and len(rows) < self.reply_lanes
               and self._queue[0][1] <= self._built):
            row = self._queue.popleft()[0]
            self._queued.discard(row[:5])
            if row[:5] not in self._ambiguous:
                rows.append(row)
        at = self.reply_at[:len(rows)]
        if rows:
            r = np.array(rows, np.int64)
            for i, c in enumerate(_COLS):
                cols[c][at] = r[:, i].astype(cols[c].dtype)
        leg = np.zeros(self.batch, np.int8)
        leg[at] = REPLY
        front_ip, front_port = cols["dst_ip"].copy(), cols["dst_port"].copy()
        if rows:
            front_ip[at] = r[:, 5].astype(front_ip.dtype)
            front_port[at] = r[:, 6].astype(front_port.dtype)
        self._sending(cols, at)
        return cols, lanes, fresh, {"leg": leg, "frontend_ip": front_ip,
                                    "frontend_port": front_port}

    def warmup(self):
        for cols in super().warmup():  # the hot ring, then next_batch's
            if not self._sent or self._sent[-1][0] is not cols:
                self._sending(cols, self.reply_at[:0])
            yield cols

    def observe(self, cols, res) -> None:
        while self._sent:
            sent, at, no = self._sent.popleft()
            if sent is cols:
                break
        else:
            raise ValueError("heard the answer to a batch this generator "
                             "did not send")
        forward = np.asarray(res.committed) == 1
        forward[at] = False  # a reply whose entry was lost is no connection
        i = np.nonzero(forward)[0]
        rows = np.stack([np.asarray(res.dnat_ip)[i], cols["src_ip"][i],
                         cols["proto"][i], np.asarray(res.dnat_port)[i],
                         cols["src_port"][i], cols["dst_ip"][i],
                         cols["dst_port"][i]], axis=1).astype(np.int64)
        rows[:, [0, 1, 5]] &= 0xFFFFFFFF
        for row in map(tuple, rows.tolist()):
            key = row[:5]
            if self._front.setdefault(key, row[5:]) != row[5:]:
                self._ambiguous.add(key)
            elif key not in self._queued and key not in self._ambiguous:
                self._queued.add(key)
                self._queue.append((row, no + 2))
        while len(self._queue) > self._backlog:
            self._queued.discard(self._queue.popleft()[0][:5])
