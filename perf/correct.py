"""The comparison that decides ``correct``: the served path against the plain
reference (perf/reference/), outside the window, in every run.

One seeded sequence of SEQ positions is the truth for everything below: the
reference runs it whole, once, on the CPU while the servers load.

*Alone*: a lane session prefills the first PROMPT positions and decodes one
step; sent twice, the replies must be the same bytes.

*Together*: one session per entry of TOGETHER prefills that many positions of
the same sequence and then decodes its steps, fed the sequence's next rows.
The first decodes twice as long as the others, which all open when it has
taken its second decode step: their prompt chunks ride steps in which it
decodes (the mixed step), then all four decode at once, so the lanes of a
batched decode step hold different lengths and contents, and a lane mixed up
with its neighbour shows. The batcher's counts over this phase go to the log
(``together_decode_batch_mean``).

Every reply row is compared with the reference's row of the same position:
max |served - reference| over max |reference|. A wrong kernel, a dropped
expert, a shifted rotary or a lane reading another's pages lands near 1. The
limits are the family's (``reference.limits``: perf/reference/<family>.py
gives each with the measurement behind it), per layer of depth, and hold for
decode rows and prefill rows separately:

- every compared row is inside ``row_bound``; a routed family may allow
  ``positions_allowed`` positions outside it (a dense family: none);
- the median of the compared rows is inside ``median_bound``, so an error
  that shifts every row a little shows though no row stands out;
- the only rows left out are those the reference itself calls near-tied (a
  router's k-th and (k+1)-th logits closer than ``tie_margin``: the served
  bf16 path may pick the other expert without a fault); a dense family leaves
  out none. At least a quarter of the rows of each kind must remain.
"""

from __future__ import annotations

import threading

import numpy as np

PROMPT, TAIL = 128, 32  # alone: the prompt, and how many of its last positions are compared
TOGETHER = ((128, 16), (120, 8), (112, 8), (104, 8))  # (prompt positions, decode steps) of each session
TOGETHER_TAIL = 8  # of each together prompt, the last rows compared
SEQ = max(prompt + steps for prompt, steps in TOGETHER)
HANDOVER_STEP = 2  # the other sessions open when this decode step of the first is done
MIN_COMPARED_SHARE = 0.25


def inputs(seed: int, hidden: int) -> np.ndarray:
    return np.random.default_rng([int(seed), 3]).standard_normal((SEQ, hidden), dtype=np.float32)


def alone(remote, x: np.ndarray) -> list:
    """[(kind, position, row)] of one session: prefill ``x[:PROMPT]``, one decode step."""
    with remote.inference_session(max_length=PROMPT + 1) as session:
        pre = np.asarray(session.step(x[None, :PROMPT]))
        dec = np.asarray(session.step(x[None, PROMPT : PROMPT + 1]))
    rows = [("prefill", p, pre[0, p]) for p in range(PROMPT - TAIL, PROMPT)]
    return rows + [("decode", PROMPT, dec[0, 0])]


def together(remote, x: np.ndarray) -> list:
    """[(kind, position, row)] of the overlapping sessions (module text)."""
    go = threading.Event()
    rows, errors, lock = [], [], threading.Lock()

    def one(k: int, prompt: int, steps: int) -> None:
        try:
            if k:
                go.wait(120)
            mine = []
            with remote.inference_session(max_length=prompt + steps) as session:
                pre = np.asarray(session.step(x[None, :prompt]))
                mine += [("prefill", p, pre[0, p]) for p in range(prompt - TOGETHER_TAIL, prompt)]
                for t in range(steps):
                    out = np.asarray(session.step(x[None, prompt + t : prompt + t + 1]))
                    mine.append(("decode", prompt + t, out[0, 0]))
                    if k == 0 and t + 1 == HANDOVER_STEP:
                        go.set()
            with lock:
                rows.extend(mine)
        except Exception as e:
            errors.append(repr(e))
        finally:
            go.set()

    threads = [threading.Thread(target=one, args=(k, *session), daemon=True) for k, session in enumerate(TOGETHER)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    if errors or any(t.is_alive() for t in threads):
        raise RuntimeError(f"check sessions failed: {errors or 'still running after 300 s'}")
    return rows


def judge(rows: list, want: np.ndarray, margin: np.ndarray, limits: dict) -> dict:
    """``rows`` [(kind, position, served row)] against ``want`` [SEQ, hidden];
    ``margin`` [SEQ] is the reference's decision margin per position and
    ``limits`` is ``reference.limits(config)``. Pure."""
    kinds = np.array([k for k, _, _ in rows])
    pos = np.array([p for _, p, _ in rows])
    got = np.stack([np.asarray(r, np.float32) for _, _, r in rows])
    finite = bool(np.isfinite(got).all())
    err = np.abs(got - want[pos]).max(-1) / np.abs(want[pos]).max(-1)
    compared = margin[pos] >= limits["tie_margin"]
    out = {"ok": finite, "finite": finite, **limits}
    for kind in ("prefill", "decode"):
        of_kind = kinds == kind
        kept = of_kind & compared
        enough = kept.sum() >= MIN_COMPARED_SHARE * of_kind.sum() and kept.sum() > 0
        outside = sorted(set(pos[kept & ~(err < limits["row_bound"])].tolist()))  # a non-finite error is outside
        median = float(np.median(err[kept])) if kept.any() else None
        ok = finite and bool(enough) and len(outside) <= limits["positions_allowed"] and median < limits["median_bound"]
        out["ok"] = out["ok"] and ok
        out[kind] = {
            "ok": ok, "rows": int(of_kind.sum()), "compared": int(kept.sum()), "positions_outside": outside,
            "median": median, "max": float(err[kept].max()) if kept.any() else None,
            "left_out_max": float(err[of_kind & ~compared].max()) if (of_kind & ~compared).any() else None,
        }
    # for a reader of the logs, and for setting the family's limits: every row
    out["rows"] = [[k, int(p), round(float(e), 5), round(float(m), 4)] for k, p, e, m in zip(kinds, pos, err, margin[pos])]
    return out


def compared(verdict: dict) -> dict:
    """``{name: [number, limit]}`` of everything ``judge`` and the repeat held a
    run to, for the result line and the last lines of standard error: a run
    that is not correct shows there which number crossed which limit. The
    largest compared row may pass the row bound where ``*_outside`` may be
    over 0; a share of rows compared is held from below, the rest from above."""
    out = {"repeat_identical": [int(verdict["repeat_identical"]), 1], "finite": [int(verdict["finite"]), 1]}
    for kind in ("prefill", "decode"):
        v = verdict[kind]
        out[f"{kind}_median"] = [v["median"], verdict["median_bound"]]
        out[f"{kind}_max"] = [v["max"], verdict["row_bound"]]
        out[f"{kind}_outside"] = [len(v["positions_outside"]), verdict["positions_allowed"]]
        out[f"{kind}_compared_share"] = [v["compared"] / v["rows"] if v["rows"] else 0.0, MIN_COMPARED_SHARE]
    return out
