"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished (drawn from the seed, the one with the
most served tokens always in it) is run through the plain reference
(``reference.py``) once, teacher-forced on prompt + served tokens.  At
every served position the reference gives the distribution the served
token should have come from, chosen by the traffic's own label and never
by what the program did: the Floe fused distribution (Eq. 14-15) for a
request labelled non-private, the SLM's own for one labelled private.
Greedy decoding serves that distribution's best token,
so the number compared is the widest gap, in nats, by which a served
token's log-probability lies below the reference's best.

Beside it, exact counts: window requests served on the other lane than
their label says, in either direction (privacy routing); requests
labelled private that fused any cloud logit; tokens of non-private
requests served without the cloud reply (the link never drops one
here); and window requests that never finished.

The control goes through the same ``compare``: with ``control=True`` the
token compared at each served position is the one the reference in
float8 puts first, in place of the served one.

Limits are data: ``limits/<cell>.json``, each with the readings it was
set from.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import numpy as np

import arrivals as A
import reference as R
import weights as W

SAMPLE_TOKENS = 320      # served tokens the reference checks per run
SAMPLE_MAX = 12          # requests at most


def load_limits(root: Path, workload: str) -> dict:
    with open(root / "limits" / f"{workload}.json") as f:
        return json.load(f)["limits"]


def draw_sample(done, seed: int) -> list:
    """The longest finished request, then others in a seed-drawn order
    until SAMPLE_TOKENS served tokens or SAMPLE_MAX requests."""
    done = [r for r in done if len(r.token_ids) > 0]
    if not done:
        return []
    done = sorted(done, key=lambda r: r.req.rid)
    longest = max(done, key=lambda r: (len(r.token_ids), -r.req.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed) % (1 << 63), 7]).permutation(
        len(rest))
    out, n = [longest], len(longest.token_ids)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(rest[i])
        n += len(rest[i].token_ids)
    return out


def reference_logprobs(cfg: dict, seed: int, sample, control: bool = False
                       ) -> List[np.ndarray]:
    """Per sampled request, (served tokens, vocab) reference
    log-probabilities at each served position."""
    import jax
    key = W.base_key(seed)
    init = cfg["weights"]
    seqs, want = [], []
    for r in sample:
        ids = A.token_ids(r.req.prompt)
        seqs.append(ids + list(r.token_ids[:-1]))
        want.append(list(range(len(ids) - 1,
                               len(ids) - 1 + len(r.token_ids))))
    pad = cfg["engine"]["max_ctx"]
    z_s = R.logits_at(key, "slm", cfg["slm"], init, seqs, want,
                      control=control, pad_to=pad)
    cloud = [i for i, r in enumerate(sample) if not r.req.private]
    z_l = R.logits_at(key, "llm", cfg["llm"], init,
                      [seqs[i] for i in cloud], [want[i] for i in cloud],
                      control=control, pad_to=pad) if cloud else []
    align = jax.jit(W.draw_align, static_argnums=(1, 2))(
        key, cfg["slm"]["vocab_size"], R.frozen(init))
    out = [np.asarray(R.slm_logprobs(z))[:len(w)]
           for z, w in zip(z_s, want)]
    for j, i in enumerate(cloud):
        out[i] = np.asarray(R.fused_logprobs(align, z_s[i], z_l[j])
                            )[:len(want[i])]
    return out


def served_gaps(logp: List[np.ndarray], served: List[List[int]]):
    """Per request: best log-probability minus the served token's."""
    return [lp.max(-1) - lp[np.arange(len(t)), np.asarray(t)]
            for lp, t in zip(logp, served)]


def compare(cfg: dict, seed: int, sample, window_records, limits: dict,
            control: bool = False) -> Dict[str, dict]:
    """Every number compared, with its limit and whether it holds.  With
    ``control`` the float8 reference's first choices stand in for the
    served tokens."""
    numbers = {}
    if sample:
        logp = reference_logprobs(cfg, seed, sample)
        if control:
            low = reference_logprobs(cfg, seed, sample, control=True)
            tokens = [list(lp.argmax(-1)) for lp in low]
        else:
            tokens = [r.token_ids for r in sample]
        gap = float(max(g.max() for g in served_gaps(logp, tokens)))
    else:
        gap = float("nan")
    numbers["served_gap_max"] = {"value": gap,
                                 "limit": limits["served_gap_max"],
                                 "ok": gap <= limits["served_gap_max"]}
    ok = [r for r in window_records if r.status == "ok"]
    misrouted = sum(1 for r in ok if r.served_private != r.req.private)
    leaked = sum(1 for r in ok if r.req.private and r.cloud_tokens > 0)
    dropped = sum(r.tokens - r.cloud_tokens for r in ok
                  if not r.req.private)
    missing = sum(1 for r in window_records if r.status != "ok")
    for name, v in (("route_mismatch", misrouted),
                    ("private_fused", leaked),
                    ("cloud_replies_lost", dropped),
                    ("never_served", missing)):
        numbers[name] = {"value": v, "limit": limits[name],
                         "ok": v <= limits[name]}
    return numbers


def is_correct(numbers: Dict[str, dict]) -> bool:
    return all(n["ok"] for n in numbers.values())
