"""The comparison that decides ``correct``.

A sample of the requests the window finished, drawn from the seed with
the one that served the most tokens and the one with the longest prompt
always in it, is run through the plain reference once, over each prompt
with its served tokens. The number compared is the widest gap by which a
served token's logit lies below the reference's best logit at that
position (greedy decoding serves the program's own argmax, so a sound
program serves the reference's best token or one within rounding of it).
"""

from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Dict, List, Sequence

from bench.lib import traffic

BENCH = Path(__file__).resolve().parents[1]
# A configuration's ``reference`` names its architecture module here: the
# plain reference forward, and what the configuration's keys mean (the
# program's model config, the served tree's layout, a step's work).
REFERENCES = BENCH / "references"


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(cfg: dict):
    """The architecture module that ``cfg["reference"]`` names."""
    return load_module(REFERENCES / f"{cfg['reference']}.py",
                       f"bench_reference_{cfg['reference']}")


def sample(requests: Sequence[dict], seed: int, n: int) -> List[dict]:
    """``n`` finished requests: the most served tokens, the longest
    prompt, then drawn from the seed."""
    done = [r for r in requests if r["generated"]]
    if not done:
        return []
    picked = [max(range(len(done)), key=lambda i: len(done[i]["generated"])),
              max(range(len(done)), key=lambda i: len(done[i]["prompt"]))]
    for i in traffic.rng(seed, 2 ** 32 + 1).permutation(len(done)):
        if len(set(picked)) >= n:
            break
        picked.append(int(i))
    return [done[i] for i in dict.fromkeys(picked)]


def padding(mix: dict) -> tuple:
    """(sequence length, rows) that every sample of the mix fits: the
    longest prompt plus the longest output, rounded up to 512, and the
    longest output."""
    n = mix["burst"]
    out = max(traffic.lengths(mix["output_len"], n))
    total = max(traffic.lengths(mix["prompt_len"], n)) + out
    return -(-total // 512) * 512, out


def readings(cfg: dict, mix: dict, seed: int, reqs: Sequence[dict],
             control: bool = False) -> Dict[str, float]:
    """``max_logit_gap``: the widest gap of a served token below the
    reference's best. With ``control``, also ``control_max_logit_gap``:
    the same for the token that the float8 control puts first."""
    ref = reference_module(cfg).Reference(cfg, seed)
    length, width = padding(mix)
    out = ref.gaps([q["prompt"] + q["generated"][:-1] for q in reqs],
                   [list(range(len(q["prompt"]) - 1,
                               len(q["prompt"]) - 1 + len(q["generated"])))
                    for q in reqs],
                   [q["generated"] for q in reqs],
                   length=length, width=width, control=control)
    res = {"max_logit_gap": max(float(g.max()) for g in out["served"]),
           "tokens_compared": sum(len(q["generated"]) for q in reqs)}
    if control:
        res["control_max_logit_gap"] = max(float(g.max()) for g in out["control"])
    return res
