"""The one general traffic generator: a traffic file of parameters in,
requests out. A later PR adds a mix by adding a file, not code.

A mix is sessions. A session has an optional context (a document), and turns;
a turn's prompt is

    [shared system prompt] + [session context] + [history, if carry_history] + [user turn]

where history is every earlier turn of the session with a stand-in answer
after each (the engine caches prompts, not answers, so a stand-in of the
answer's length shares exactly what the real answer would). Every piece of
a prompt is a SEGMENT (key, length); two prompts share a prefix exactly
where they share leading segments, and a segment's tokens come from
(--seed, key) alone.

The SHAPE of the schedule (which request is due when, every length, which
turn follows which) comes from the file's `schedule_seed`, not from --seed:
every seed then does the same work in the same order, and only the token
ids (and the weights) differ. A seed that changed the sizes would be a
different workload each run, and the spread between seeds would hide what a
PR did.

Parameters of a traffic file read here (all lengths in tokens):
  loop                "open" (arrivals) | "closed" (clients)
  rate_rps            open: mean arrival rate, Poisson
  clients             closed: number of clients, each with its own sessions
  shared_prefix_tokens
  context_tokens      dist, optional (a document per session)
  user_turn_tokens    dist
  answer_tokens       dist  (max_new_tokens of the request; no EOS)
  turns_per_session   closed: fixed count; open: ignored (followup_share decides)
  stagger_first_session  closed: client c's FIRST session is cut to turns - (c * turns // clients)
                      turns, so that at any instant the clients stand spread evenly over a
                      session's turns (an eighth of them at each of 8) instead of walking
                      through it in step; by turns, not by seconds, so it holds for a faster
                      or slower program alike. Off where the key is absent.
  followup_share      open: probability that an arrival continues an open session
  followup_min_gap_s  open: a session is continued only this long after its last turn was due
  carry_history       whether a turn's prompt holds the session's earlier turns
  max_prompt_tokens   a turn that would exceed it starts a new session instead
  dist = {"dist": "const"|"uniform"|"loguniform", "value"| "min","max"}
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

Segment = Tuple[str, int]  # (key, n_tokens)


@dataclasses.dataclass
class Request:
    idx: int
    due_s: Optional[float]  # open loop: seconds after the schedule starts
    client: Optional[int]  # closed loop: which client sends it, in list order
    segments: List[Segment]
    max_new_tokens: int

    @property
    def prompt_tokens(self) -> int:
        return sum(n for _k, n in self.segments)


def _draw(rng: np.random.Generator, dist: Dict[str, Any]) -> int:
    kind = dist["dist"]
    if kind == "const":
        return int(dist["value"])
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "uniform":
        return int(rng.integers(lo, hi + 1))
    if kind == "loguniform":
        return int(round(math.exp(rng.uniform(math.log(lo), math.log(hi)))))
    raise ValueError(f"unknown dist {kind!r}")


def _bounds(dist: Optional[Dict[str, Any]]) -> Tuple[int, int]:
    if not dist:
        return 0, 0
    if dist["dist"] == "const":
        return int(dist["value"]), int(dist["value"])
    return int(dist["min"]), int(dist["max"])


class _Session:
    def __init__(self, sid: str, shared: int, context: int):
        self.sid = sid
        self.base: List[Segment] = ([("shared", shared)] if shared else []) + (
            [(f"{sid}.ctx", context)] if context else []
        )
        self.history: List[Segment] = []
        self.turns = 0
        self.last_due = 0.0

    def next_prompt(self, turn_len: int, answer_len: int, carry: bool) -> List[Segment]:
        turn = (f"{self.sid}.u{self.turns}", turn_len)
        prompt = self.base + (self.history if carry else []) + [turn]
        self.history = self.history + [turn, (f"{self.sid}.a{self.turns}", answer_len)]
        self.turns += 1
        return prompt

    def next_len(self, turn_len: int, carry: bool) -> int:
        return sum(n for _k, n in self.base + (self.history if carry else [])) + turn_len


def generate(traffic: Dict[str, Any], horizon_s: float) -> List[Request]:
    """Open loop: every request due in [0, horizon_s). Closed loop: per
    client more requests than `horizon_s` can possibly serve (a request
    takes at least 50 ms here), in the order each client sends them."""
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    shared = int(traffic.get("shared_prefix_tokens", 0))
    carry = bool(traffic.get("carry_history", False))
    cap = int(traffic.get("max_prompt_tokens", 1 << 30))
    out: List[Request] = []

    def new_session(i: int) -> _Session:
        ctx = _draw(rng, traffic["context_tokens"]) if traffic.get("context_tokens") else 0
        return _Session(f"s{i}", shared, ctx)

    if traffic["loop"] == "open":
        sessions: List[_Session] = []
        t = 0.0
        while True:
            t += float(rng.exponential(1.0 / float(traffic["rate_rps"])))
            if t >= horizon_s:
                break
            turn_len = _draw(rng, traffic["user_turn_tokens"])
            answer_len = _draw(rng, traffic["answer_tokens"])
            follow = rng.random() < float(traffic.get("followup_share", 0.0))
            pick = rng.random()
            open_ = [
                s for s in sessions
                if t - s.last_due >= float(traffic.get("followup_min_gap_s", 0.0))
                and s.next_len(turn_len, carry) <= cap
            ]
            if follow and open_:
                s = open_[int(pick * len(open_))]
            else:
                s = new_session(len(sessions))
                sessions.append(s)
            s.last_due = t
            out.append(Request(len(out), t, None, s.next_prompt(turn_len, answer_len, carry), answer_len))
        return out

    n_clients = int(traffic["clients"])
    per_client = int(horizon_s / 0.05 / max(1, n_clients)) + 8
    turns = int(traffic.get("turns_per_session", 1))
    stagger = bool(traffic.get("stagger_first_session", False))
    n_sessions = 0
    for c in range(n_clients):
        made = 0
        while made < per_client:
            s = new_session(n_sessions)
            n_sessions += 1
            for _ in range(turns - (c * turns // n_clients if stagger and made == 0 else 0)):
                turn_len = _draw(rng, traffic["user_turn_tokens"])
                answer_len = _draw(rng, traffic["answer_tokens"])
                if s.next_len(turn_len, carry) > cap:
                    break
                out.append(Request(len(out), None, c, s.next_prompt(turn_len, answer_len, carry), answer_len))
                made += 1
    return out


def segment_tokens(seed: int, key: str, n: int, vocab: int) -> np.ndarray:
    """Token ids of one segment: a function of (--seed, key) only, so equal
    segments are equal tokens and a prefix is shared exactly when its
    segments are. Token 0 is left out (the engine's pad)."""
    import zlib

    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, zlib.crc32(key.encode())])
    return rng.integers(1, vocab, n, dtype=np.int32)


def prompt_tokens(req: Request, seed: int, vocab: int) -> List[int]:
    return np.concatenate([segment_tokens(seed, k, n, vocab) for k, n in req.segments]).tolist()


def prompt_length_range(traffic: Dict[str, Any]) -> Tuple[int, int]:
    """Shortest and longest prompt the FILE can produce (not what one
    schedule happened to draw): what warm-up has to cover."""
    shared = int(traffic.get("shared_prefix_tokens", 0))
    c_lo, c_hi = _bounds(traffic.get("context_tokens"))
    u_lo, u_hi = _bounds(traffic["user_turn_tokens"])
    _a_lo, a_hi = _bounds(traffic["answer_tokens"])
    lo = shared + c_lo + u_lo
    hi = shared + c_hi + u_hi
    if traffic.get("carry_history"):
        if "max_prompt_tokens" in traffic:
            hi = int(traffic["max_prompt_tokens"])
        else:
            turns = int(traffic.get("turns_per_session", 1))
            hi = shared + c_hi + turns * u_hi + (turns - 1) * a_hi
    elif "max_prompt_tokens" in traffic:
        hi = min(hi, int(traffic["max_prompt_tokens"]))
    return lo, hi


def max_answer_tokens(traffic: Dict[str, Any]) -> int:
    """The longest answer the FILE can ask for."""
    return _bounds(traffic["answer_tokens"])[1]


def bucket_pages(n_pages: int, max_pages_per_seq: int) -> int:
    """The benchmark's copy of PagedLM's bucket rule (power of two pages,
    capped), for the rehearsal and the tests; the replica warms up with the
    program's own rule and a test holds the two together."""
    return min(max_pages_per_seq, 1 << max(0, math.ceil(math.log2(n_pages))))


def prefill_buckets(traffic: Dict[str, Any], page_tokens: int, max_pages_per_seq: int) -> List[int]:
    lo, hi = prompt_length_range(traffic)
    pages = range(max(1, -(-lo // page_tokens)), max(1, -(-hi // page_tokens)) + 1)
    return sorted({bucket_pages(p, max_pages_per_seq) for p in pages})
