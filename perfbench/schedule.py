"""The one general traffic generator: a traffic file -> a list of requests.

A traffic mix is data (``perfbench/traffic/<name>.json``); this file is the
only code that reads one. Everything is a *session*: a shared prefix of
``doc_len`` tokens asked about ``questions`` times, each question a fresh
prompt appended to the prefix. Plain chat is the session with no prefix and
one question. Sessions start as a Poisson process whose rate is the file's
request rate over the mean number of questions.

Two seeds, kept apart on purpose:

* ``schedule_seed`` (a constant in the traffic file) decides arrival
  offsets, every length, and which session a request belongs to. ``plan``
  is a pure function of the file and the window length, so every run of a
  cell sends the same requests at the same offsets.
* ``--seed`` decides every token id (``fill``), and the weights.

Each session consumes a fixed number of draws, so a longer window extends
the plan and never reshuffles it.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

_MAX_SESSIONS = 1_000_000


def _pick(rng, values, weights=None):
    p = None
    if weights is not None:
        p = np.asarray(weights, float)
        p = p / p.sum()
    return int(rng.choice(np.asarray(values), p=p))


def plan(traffic: Dict, seconds: float) -> List[Dict]:
    """Requests in due order. ``due`` is the offset in seconds from the
    generator's time zero; ``counted`` marks those due inside the window
    ``[lead_in_s, lead_in_s + seconds)``."""
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    ses = traffic["session"]
    lead_in = float(traffic["lead_in_s"])
    horizon = lead_in + float(seconds) + float(traffic["lead_out_s"])
    n_max = max(ses["questions"])
    q_w = ses.get("question_weights")
    mean_q = float(np.average(ses["questions"], weights=q_w))
    session_rate = float(traffic["rate_rps"]) / mean_q
    lo, hi = ses["gap_s"]

    out: List[Dict] = []
    start = -float(ses.get("backfill_s", 0.0))
    for sid in range(_MAX_SESSIONS):
        # a fixed number of draws per session, whatever it ends up using
        start += float(rng.exponential(1.0 / session_rate))
        doc_len = _pick(rng, ses["doc_lens"], ses.get("doc_weights"))
        n_q = _pick(rng, ses["questions"], q_w)
        gaps = rng.uniform(lo, hi, size=n_max)
        p_lens = [_pick(rng, traffic["prompt_lens"],
                        traffic.get("prompt_weights")) for _ in range(n_max)]
        a_lens = [_pick(rng, traffic["answer_lens"],
                        traffic.get("answer_weights")) for _ in range(n_max)]
        if start >= horizon:
            break
        due = start
        for k in range(n_q):
            if k:
                due += float(gaps[k])
            if 0.0 <= due < horizon:
                out.append({"due": due, "session": sid, "question": k,
                            "doc_len": doc_len, "prompt_len": doc_len + p_lens[k],
                            "new_tokens": a_lens[k]})
    out.sort(key=lambda r: (r["due"], r["session"], r["question"]))
    for i, r in enumerate(out):
        r["id"] = i
        r["counted"] = bool(lead_in <= r["due"] < lead_in + float(seconds))
    return out


def fill(requests: List[Dict], seed: int, vocab: int) -> List[Dict]:
    """Give every request its token ids from ``seed``: one stream per
    session for the shared prefix, one per request for the question."""
    docs: Dict[int, np.ndarray] = {}
    for r in requests:
        sid, doc_len = r["session"], r["doc_len"]
        if doc_len and sid not in docs:
            docs[sid] = np.random.default_rng([int(seed), 1, sid]).integers(
                0, vocab, doc_len, dtype=np.int64)
        q = np.random.default_rng(
            [int(seed), 2, sid, r["question"]]).integers(
            0, vocab, r["prompt_len"] - doc_len, dtype=np.int64)
        ids = np.concatenate([docs[sid], q]) if doc_len else q
        r["prompt"] = ids.tolist()
    return requests


def prompt_shapes(requests: List[Dict]) -> Dict[str, List[int]]:
    """What set-up has to warm for this plan: the distinct full prompt
    lengths, and the distinct (prefix, tail) pairs of repeated prefixes."""
    full = sorted({r["prompt_len"] for r in requests})
    tails = sorted({(r["doc_len"], r["prompt_len"] - r["doc_len"])
                    for r in requests if r["doc_len"] and r["question"]})
    return {"prompt_lens": full, "tails": [list(t) for t in tails]}
