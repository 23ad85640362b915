"""Random hidden ground truths over random port lattices, for the tests.

The program's generator (``repro.core.uarch.generate_isa_and_uarch``) draws
on a fixed core's port sets; the property tests also need arbitrary port
combinations, so they draw them here.
"""
import random

from repro.core.isa import GPR, ISA, InstrSpec, op
from repro.core.uarch import InstrBehavior, UArch, beh, uop


def random_uarch_and_isa(seed: int, n_instr: int = 6,
                         ports: tuple[str, ...] = tuple("012345")):
    """Draw a random hidden ground truth plus an ISA guaranteed to contain a
    1-μop blocking instruction for every functional-unit port combination
    (the paper's §5.1.1 assumption). Returns (uarch, isa, truth) where
    ``truth[name]`` is the port-usage multiset {frozenset: count}."""
    rng = random.Random(seed)
    n_pc = rng.randint(2, 4)
    pcs: list[frozenset] = []
    while len(pcs) < n_pc:
        k = rng.randint(1, min(3, len(ports)))
        pc = frozenset(rng.sample(ports, k))
        if pc not in pcs:
            pcs.append(pc)
    isa = ISA()
    behaviors: dict[str, InstrBehavior] = {}
    truth: dict[str, dict[frozenset, int]] = {}
    # blocking candidates: one 1-uop instr per combination
    for i, pc in enumerate(pcs):
        nm = f"BLK{i}"
        isa.add(InstrSpec(nm, nm, (op("op1", GPR, "w"), op("op2", GPR, "r"))))
        behaviors[nm] = beh(uop(pc, ("op2",), ("op1",)))
        truth[nm] = {pc: 1}
    # random multi-uop instructions over those combinations
    for i in range(n_instr):
        nm = f"INS{i}"
        k = rng.randint(1, 3)
        usage: dict[frozenset, int] = {}
        uops = []
        for j in range(k):
            pc = rng.choice(pcs)
            usage[pc] = usage.get(pc, 0) + 1
            reads = ("op2",) if j == 0 else (f"%{j-1}",)
            writes = ("op1",) if j == k - 1 else (f"%{j}",)
            uops.append(uop(pc, reads, writes, rng.randint(1, 4)))
        isa.add(InstrSpec(nm, nm, (op("op1", GPR, "w"), op("op2", GPR, "r"))))
        behaviors[nm] = InstrBehavior(tuple(uops))
        truth[nm] = usage
    ua = UArch(f"rand{seed}", ports, 6, behaviors, overhead_cycles=50)
    return ua, isa, truth
