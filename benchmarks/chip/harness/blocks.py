"""Seeded basic blocks for the ground-truth and serving cells.

The five families of the program's corpus generator (``corpus/generate.py``
at commit 4c81949: dependency chains, port pressure, mixed, divider-heavy,
zero idioms and eliminable moves), copied over the benchmark's own tables
so that the traffic does not move when the program does.  One change: each
seed draws the same multiset of (family, length) pairs, only their order
and contents differ, so every seed gives the same amount of work.

A block is a tuple of ``(spec, regs, hint)`` instructions; :func:`text`
gives the program's textual block format (``SPEC op1=R3 op2=R7 !high``).
"""
from __future__ import annotations

import random

from reference.oracle import FLAGS, GPR, IMM, MEM, VEC

FAMILIES = ("dep_chain", "port_pressure", "mixed", "divider", "idiom")

_POOLS = {
    GPR: [f"R{i}" for i in range(16)],
    VEC: [f"X{i}" for i in range(16)],
    MEM: [f"RB{i}" for i in range(8)],
}
_TIGHT_POOLS = {
    GPR: [f"R{i}" for i in range(6)],
    VEC: [f"X{i}" for i in range(6)],
    MEM: [f"RB{i}" for i in range(4)],
}


def variant_pool(isa: dict, uarch) -> list:
    return sorted(n for n, s in isa.items()
                  if n in uarch.behaviors and s.characterized)


def _regs_for(spec, rng, pools) -> dict:
    return {o.name: rng.choice(pools[o.otype]) for o in spec.explicit
            if o.otype not in (IMM, FLAGS)}


def _chainable(spec):
    rw = [o for o in spec.explicit
          if o.otype in (GPR, VEC) and o.read and o.written]
    if rw:
        return rw[0]
    r = [o for o in spec.explicit if o.otype in (GPR, VEC) and o.read]
    return r[0] if r else None


def _written(spec):
    for o in spec.explicit:
        if o.otype in (GPR, VEC) and o.written:
            return o
    return None


def _dep_chain(isa, pool, rng, length, ua):
    names = [n for n in pool if _chainable(isa[n]) is not None]
    prev = {GPR: "R0", VEC: "X0"}
    code = []
    for _ in range(length):
        spec = isa[rng.choice(names)]
        link = _chainable(spec)
        regs = _regs_for(spec, rng, _TIGHT_POOLS)
        regs[link.name] = prev[link.otype]
        code.append((spec.name, regs, "low"))
        out = _written(spec)
        if out is not None:
            prev[out.otype] = regs.get(out.name, prev[out.otype])
    return code


def _port_pressure(isa, pool, rng, length, ua):
    groups: dict = {}
    for n in pool:
        sig = frozenset(p for u in ua.behaviors[n].uops for p in u.ports)
        groups.setdefault(sig, []).append(n)
    sigs = sorted(groups, key=lambda s: (len(s), sorted(s)))
    names = groups[sigs[min(int(rng.expovariate(0.7)), len(sigs) - 1)]]
    code = []
    for i in range(length):
        spec = isa[rng.choice(names)]
        regs = {}
        for j, o in enumerate(spec.explicit):
            if o.otype in (IMM, FLAGS):
                continue
            p = _POOLS[o.otype]
            regs[o.name] = p[(2 * i + j) % len(p)]
        code.append((spec.name, regs, "low"))
    return code


def _mixed(isa, pool, rng, length, ua):
    code = []
    for _ in range(length):
        spec = isa[rng.choice(pool)]
        hint = "high" if spec.uses_divider and rng.random() < 0.3 else "low"
        code.append((spec.name, _regs_for(spec, rng, _POOLS), hint))
    return code


def _divider(isa, pool, rng, length, ua):
    divs = [n for n in pool if isa[n].uses_divider]
    code = []
    for _ in range(length):
        if rng.random() < 0.6:
            spec = isa[rng.choice(divs)]
            hint = "high" if rng.random() < 0.5 else "low"
        else:
            spec = isa[rng.choice(pool)]
            hint = "low"
        code.append((spec.name, _regs_for(spec, rng, _TIGHT_POOLS), hint))
    return code


def _idiom(isa, pool, rng, length, ua):
    idioms = [n for n in pool if isa[n].zero_idiom]
    moves = [n for n in pool if isa[n].may_eliminate]
    code = _dep_chain(isa, pool, rng, length, ua)
    for i in range(len(code)):
        roll = rng.random()
        if idioms and roll < 0.3:
            spec = isa[rng.choice(idioms)]
            reg = rng.choice(_TIGHT_POOLS[spec.explicit[0].otype])
            regs = {o.name: reg for o in spec.explicit
                    if o.otype not in (IMM, FLAGS)}
            code[i] = (spec.name, regs, "low")
        elif moves and roll < 0.5:
            spec = isa[rng.choice(moves)]
            code[i] = (spec.name, _regs_for(spec, rng, _TIGHT_POOLS), "low")
    return code


_GENERATORS = {"dep_chain": _dep_chain, "port_pressure": _port_pressure,
               "mixed": _mixed, "divider": _divider, "idiom": _idiom}


def generate(isa: dict, uarch, n: int, seed: int, *, min_len: int = 2,
             max_len: int = 12, tag: str = "blocks") -> list:
    """``n`` blocks for ``uarch``: families and lengths cycle evenly over
    ``FAMILIES`` and ``min_len..max_len`` (the same multiset for every
    seed), shuffled and filled from ``random.Random`` seeded by
    ``(tag, seed, uarch)`` (string seeds go through SHA-512: the same on
    every host)."""
    rng = random.Random(f"bench-{tag}/{seed}/{uarch.name}")
    pool = variant_pool(isa, uarch)
    span = max_len - min_len + 1
    plan = [(FAMILIES[i % len(FAMILIES)], min_len + (i // len(FAMILIES))
             % span) for i in range(n)]
    rng.shuffle(plan)
    return [tuple(_GENERATORS[fam](isa, pool, rng, length, uarch))
            for fam, length in plan]


def text(block) -> str:
    lines = []
    for spec, regs, hint in block:
        toks = [spec] + [f"{k}={v}" for k, v in regs.items()]
        if hint != "low":
            toks.append(f"!{hint}")
        lines.append(" ".join(toks))
    return "\n".join(lines)
