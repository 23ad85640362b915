"""``RegPool.take`` hands out exactly the round robin of a plain
``itertools.cycle`` walk, for every operand type, ``avoid`` set and
interleaving of requests."""
import itertools
import random

import pytest

from repro.core.isa import FLAGS, GPR, IMM, MEM, TEST_ISA, VEC
from repro.core.machine import RegPool, fresh_instance

TYPES = (GPR, VEC, MEM, FLAGS, IMM)


class CyclePool:
    """The plain reference: one ``itertools.cycle`` per operand type,
    advanced until a name outside ``avoid`` comes up, giving up after four
    laps."""

    def __init__(self, n_gpr=16, n_vec=16, n_mem=8):
        self.names = {GPR: [f"R{i}" for i in range(n_gpr)],
                      VEC: [f"X{i}" for i in range(n_vec)],
                      MEM: [f"RB{i}" for i in range(n_mem)],
                      FLAGS: ["FLAGS"], IMM: ["IMM"]}
        self.iters = {}

    def take(self, otype, avoid=frozenset()):
        names = self.names[otype]
        it = self.iters.setdefault(otype, itertools.cycle(names))
        for _ in range(4 * len(names)):
            r = next(it)
            if r not in avoid:
                return r
        raise RuntimeError(f"register pool exhausted for {otype}")


def _avoid(rng, names):
    """A random ``avoid`` set: empty, a few names, most of them or all."""
    k = rng.choice([0, 0, 1, 2, 3, len(names) - 1, len(names)])
    return set(rng.sample(names, max(0, min(k, len(names)))))


@pytest.mark.parametrize("seed", range(6))
def test_take_matches_cycle_reference(seed):
    rng = random.Random(seed)
    sizes = rng.choice([(16, 16, 8), (4, 3, 2), (1, 2, 1)])
    pool, ref = RegPool(*sizes), CyclePool(*sizes)
    raised = 0
    for _ in range(3000):
        otype = rng.choice(TYPES)
        avoid = _avoid(rng, ref.names[otype])
        try:
            want = ref.take(otype, avoid)
        except RuntimeError:
            with pytest.raises(RuntimeError, match="exhausted"):
                pool.take(otype, avoid)
            raised += 1
            continue
        assert pool.take(otype, avoid) == want
    assert raised > 0   # fully avoided types were exercised


def test_fully_avoided_type_raises_and_keeps_its_place():
    pool, ref = RegPool(), CyclePool()
    for otype in TYPES:
        names = ref.names[otype]
        assert pool.take(otype) == ref.take(otype)
        with pytest.raises(RuntimeError, match=f"exhausted for {otype}"):
            pool.take(otype, set(names))
        with pytest.raises(RuntimeError):
            ref.take(otype, set(names))
        # the failed request moved neither pool
        assert pool.take(otype) == ref.take(otype)


def test_fresh_instance_sequence_unchanged():
    """Instances built from one pool, with and without ``avoid``: distinct
    registers per operand, in the reference's round-robin order."""
    pool, ref = RegPool(), CyclePool()
    rng = random.Random(7)
    specs = [s for s in TEST_ISA if s.characterized]
    for _ in range(500):
        spec = rng.choice(specs)
        avoid = set(rng.sample(ref.names[GPR], rng.randint(0, 4)))
        got = fresh_instance(spec, pool, avoid)
        used, want = set(avoid), {}
        for o in spec.operands:
            if o.implicit or o.otype == IMM:
                continue
            want[o.name] = ref.take(o.otype, used)
            used.add(want[o.name])
        assert got.spec == spec.name and got.regs == want
