"""Machine-readable ISA description (the paper's XED→XML analogue, §6.1).

The paper extracts a machine-readable description of the x86 instruction set
from Intel XED's configuration files because the microbenchmark *generators*
(§5.2) need to know, for every instruction: the explicit and implicit
operands, their types and widths, which are read / written / both, and
special semantics (zero idioms, move elimination candidates, divider usage,
serializing/system instructions, control flow).

Here the same information lives in :class:`InstrSpec` records. The registry
is the single source of truth used by

  * the microbenchmark generators (blocking/latency/throughput),
  * the simulated machine's ground-truth tables (core/uarch.py),
  * the XML/JSON export (core/model_io.py).

Register classes model the structure that drives the paper's case analysis
in §5.2: gpr / vec / flags / mem, plus operand widths (partial-register
handling) and read-modify-write flags (the "both read and written" case).
"""
from __future__ import annotations

import functools
import random
from dataclasses import dataclass, replace
from typing import Iterable


# operand types
GPR = "gpr"
VEC = "vec"
FLAGS = "flags"
MEM = "mem"
IMM = "imm"


@dataclass(frozen=True)
class Operand:
    name: str          # "op1", "op2", "flags", "mem", ...
    otype: str         # gpr | vec | flags | mem | imm
    read: bool
    written: bool
    implicit: bool = False
    width: int = 64

    @property
    def rmw(self) -> bool:
        return self.read and self.written


def op(name, otype, mode, implicit=False, width=64) -> Operand:
    """mode: 'r' | 'w' | 'rw'."""
    return Operand(name, otype, "r" in mode, "w" in mode, implicit, width)


@dataclass(frozen=True)
class InstrSpec:
    name: str                      # unique variant name, e.g. "ADD_R64_R64"
    mnemonic: str
    operands: tuple[Operand, ...]
    uses_divider: bool = False
    serializing: bool = False
    system: bool = False
    control_flow: bool = False
    may_eliminate: bool = False    # reg-reg move elimination candidate
    zero_idiom: bool = False       # same-reg => breaks dependency
    is_nop: bool = False
    extension: str = "BASE"        # BASE | SSE | AVX  (§5.1.1 transition penalties)

    @property
    def sources(self) -> tuple[Operand, ...]:
        return tuple(o for o in self.operands if o.read)

    @property
    def dests(self) -> tuple[Operand, ...]:
        return tuple(o for o in self.operands if o.written)

    @property
    def explicit_operands(self) -> tuple[Operand, ...]:
        return tuple(o for o in self.operands if not o.implicit)

    @property
    def characterized(self) -> bool:
        """Paper §8 limitations: NOPs and system, serializing and
        control-flow instructions are not characterized."""
        return not (self.system or self.serializing or self.control_flow
                    or self.is_nop)

    def reads_flags(self) -> bool:
        return any(o.otype == FLAGS and o.read for o in self.operands)

    def writes_flags(self) -> bool:
        return any(o.otype == FLAGS and o.written for o in self.operands)

    def replace(self, **kw) -> "InstrSpec":
        return replace(self, **kw)


class ISA:
    """A registry of instruction variants (one x86-like μISA instance)."""

    def __init__(self, specs: Iterable[InstrSpec] = ()):  # noqa: D107
        self._specs: dict[str, InstrSpec] = {}
        for s in specs:
            self.add(s)

    def add(self, spec: InstrSpec) -> None:
        if spec.name in self._specs:
            raise ValueError(f"duplicate instruction {spec.name}")
        self._specs[spec.name] = spec

    def __getitem__(self, name: str) -> InstrSpec:
        return self._specs[name]

    def __contains__(self, name: str) -> bool:
        return name in self._specs

    def __iter__(self):
        return iter(self._specs.values())

    def __len__(self) -> int:
        return len(self._specs)

    def names(self) -> list[str]:
        return list(self._specs)


# ---------------------------------------------------------------------------
# the test μISA — an x86-flavored instruction set exercising every structural
# case from §5.2: implicit flags, RMW operands, type-crossing moves, loads,
# stores, dividers, zero idioms, eliminable movs, chain instructions.
# ---------------------------------------------------------------------------

_F = op("flags", FLAGS, "w", implicit=True)
_Frw = op("flags", FLAGS, "rw", implicit=True)
_Fr = op("flags", FLAGS, "r", implicit=True)


def _alu2(name, *, flags="w", zero_idiom=False, ext="BASE"):
    """Two-operand ALU: op1 rw, op2 r, writes (or rw) flags."""
    ops = [op("op1", GPR, "rw"), op("op2", GPR, "r")]
    if flags == "w":
        ops.append(_F)
    elif flags == "rw":
        ops.append(_Frw)
    return InstrSpec(name=f"{name}_R64_R64", mnemonic=name,
                     operands=tuple(ops), zero_idiom=zero_idiom, extension=ext)


def build_test_isa() -> ISA:
    isa = ISA()
    # --- integer ALU ---
    for nm in ("ADD", "SUB", "AND", "OR"):
        isa.add(_alu2(nm))
    isa.add(_alu2("XOR", zero_idiom=True))
    isa.add(_alu2("SUBZ", zero_idiom=True))  # second zero idiom (SUB-like)
    isa.add(_alu2("ADC", flags="rw"))        # reads+writes flags (carry)
    isa.add(_alu2("SBB", flags="rw"))
    isa.add(InstrSpec("CMP_R64_R64", "CMP",
                      (op("op1", GPR, "r"), op("op2", GPR, "r"), _F)))
    isa.add(InstrSpec("TEST_R64_R64", "TEST",
                      (op("op1", GPR, "r"), op("op2", GPR, "r"), _F)))
    isa.add(InstrSpec("INC_R64", "INC", (op("op1", GPR, "rw"), _F)))
    isa.add(InstrSpec("NOT_R64", "NOT", (op("op1", GPR, "rw"),)))
    isa.add(InstrSpec("LEA_R64", "LEA",
                      (op("op1", GPR, "w"), op("op2", GPR, "r"))))
    isa.add(InstrSpec("POPCNT_R64_R64", "POPCNT",
                      (op("op1", GPR, "w"), op("op2", GPR, "r"), _F)))
    isa.add(InstrSpec("BSWAP_R32", "BSWAP", (op("op1", GPR, "rw", width=32),)))
    isa.add(InstrSpec("BSWAP_R64", "BSWAP", (op("op1", GPR, "rw"),)))
    # --- moves ---
    isa.add(InstrSpec("MOV_R64_R64", "MOV",
                      (op("op1", GPR, "w"), op("op2", GPR, "r")),
                      may_eliminate=True))
    isa.add(InstrSpec("MOVSX_R64_R32", "MOVSX",
                      (op("op1", GPR, "w"), op("op2", GPR, "r", width=32))))
    isa.add(InstrSpec("MOVSX_R64_R8", "MOVSX",
                      (op("op1", GPR, "w"), op("op2", GPR, "r", width=8))))
    isa.add(InstrSpec("MOVZX_R64_R16", "MOVZX",
                      (op("op1", GPR, "w"), op("op2", GPR, "r", width=16)),
                      may_eliminate=True))
    # --- shifts / rotates (implicit flags RMW; SHLD same-reg special) ---
    for nm in ("SHL", "SHR", "SAR", "ROL", "ROR"):
        isa.add(InstrSpec(f"{nm}_R64_I8", nm,
                          (op("op1", GPR, "rw"), op("imm", IMM, "r"), _Frw)))
    isa.add(InstrSpec("SHLD_R64_R64_I8", "SHLD",
                      (op("op1", GPR, "rw"), op("op2", GPR, "r"),
                       op("imm", IMM, "r"), _F)))
    # --- multiply / divide ---
    isa.add(InstrSpec("IMUL_R64_R64", "IMUL",
                      (op("op1", GPR, "rw"), op("op2", GPR, "r"), _F)))
    isa.add(InstrSpec("MUL_R64", "MUL",
                      (op("op1", GPR, "rw"), op("op2", GPR, "r"),
                       op("hi", GPR, "w", implicit=True), _F)))
    isa.add(InstrSpec("DIV_R64", "DIV",
                      (op("op1", GPR, "rw"), op("op2", GPR, "r"),
                       op("hi", GPR, "rw", implicit=True), _F),
                      uses_divider=True))
    # --- condition-flag consumers ---
    isa.add(InstrSpec("SETC_R8", "SETC",
                      (op("op1", GPR, "w", width=8), _Fr)))
    isa.add(InstrSpec("CMOVBE_R64_R64", "CMOVBE",
                      (op("op1", GPR, "rw"), op("op2", GPR, "r"), _Fr)))
    isa.add(InstrSpec("CMC", "CMC", (_Frw,)))
    isa.add(InstrSpec("SAHF", "SAHF",
                      (op("op1", GPR, "r", width=8), _F)))
    # --- memory ---
    isa.add(InstrSpec("MOV_R64_M64", "MOV",
                      (op("op1", GPR, "w"), op("mem", MEM, "r"))))
    isa.add(InstrSpec("MOV_M64_R64", "MOV",
                      (op("mem", MEM, "w"), op("op1", GPR, "r"))))
    isa.add(InstrSpec("ADD_R64_M64", "ADD",
                      (op("op1", GPR, "rw"), op("mem", MEM, "r"), _F)))
    isa.add(InstrSpec("IMUL_R64_M64", "IMUL",
                      (op("op1", GPR, "rw"), op("mem", MEM, "r"), _F)))
    # --- vector (SSE-like and AVX-like for the two blocking sets) ---
    for ext, pre in (("SSE", "P"), ("AVX", "VP")):
        isa.add(InstrSpec(f"{pre}ADDD_X_X", f"{pre}ADDD",
                          (op("op1", VEC, "rw"), op("op2", VEC, "r")),
                          extension=ext))
        isa.add(InstrSpec(f"{pre}MULD_X_X", f"{pre}MULD",
                          (op("op1", VEC, "rw"), op("op2", VEC, "r")),
                          extension=ext))
        isa.add(InstrSpec(f"{pre}SHUFB_X_X", f"{pre}SHUFB",
                          (op("op1", VEC, "rw"), op("op2", VEC, "r")),
                          extension=ext))
        isa.add(InstrSpec(f"{pre}AND_X_X", f"{pre}AND",
                          (op("op1", VEC, "rw"), op("op2", VEC, "r")),
                          extension=ext))
        isa.add(InstrSpec(f"{pre}CMPGTQ_X_X", f"{pre}CMPGTQ",
                          (op("op1", VEC, "rw"), op("op2", VEC, "r")),
                          zero_idiom=True, extension=ext))
    isa.add(InstrSpec("SHUFPS_X_X", "SHUFPS",
                      (op("op1", VEC, "rw"), op("op2", VEC, "r")),
                      extension="SSE"))
    # non-destructive shuffles: the §5.2.1 SIMD chain instructions
    isa.add(InstrSpec("PSHUFD_X_X", "PSHUFD",
                      (op("op1", VEC, "w"), op("op2", VEC, "r")),
                      extension="SSE"))
    isa.add(InstrSpec("MOVSHDUP_X_X", "MOVSHDUP",
                      (op("op1", VEC, "w"), op("op2", VEC, "r")),
                      extension="SSE"))
    isa.add(InstrSpec("ADDPS_X_X", "ADDPS",
                      (op("op1", VEC, "rw"), op("op2", VEC, "r")),
                      extension="SSE"))
    isa.add(InstrSpec("MULPS_X_X", "MULPS",
                      (op("op1", VEC, "rw"), op("op2", VEC, "r")),
                      extension="SSE"))
    isa.add(InstrSpec("DIVPS_X_X", "DIVPS",
                      (op("op1", VEC, "rw"), op("op2", VEC, "r")),
                      uses_divider=True, extension="SSE"))
    isa.add(InstrSpec("AESDEC_X_X", "AESDEC",
                      (op("op1", VEC, "rw"), op("op2", VEC, "r")),
                      extension="SSE"))
    isa.add(InstrSpec("AESDEC_X_M", "AESDEC",
                      (op("op1", VEC, "rw"), op("mem", MEM, "r")),
                      extension="SSE"))
    isa.add(InstrSpec("MOVQ2DQ_X_X", "MOVQ2DQ",
                      (op("op1", VEC, "w"), op("op2", VEC, "r")),
                      extension="SSE"))
    isa.add(InstrSpec("MOVAPS_X_X", "MOVAPS",
                      (op("op1", VEC, "w"), op("op2", VEC, "r")),
                      may_eliminate=True, extension="SSE"))
    # --- type-crossing (vec <-> gpr): chain-instruction candidates §5.2.1 ---
    isa.add(InstrSpec("MOVD_R64_X", "MOVD",
                      (op("op1", GPR, "w"), op("op2", VEC, "r")),
                      extension="SSE"))
    isa.add(InstrSpec("MOVD_X_R64", "MOVD",
                      (op("op1", VEC, "w"), op("op2", GPR, "r")),
                      extension="SSE"))
    isa.add(InstrSpec("PEXTRQ_R64_X", "PEXTRQ",
                      (op("op1", GPR, "w"), op("op2", VEC, "r")),
                      extension="SSE"))
    # --- stores with data computation / vector store ---
    isa.add(InstrSpec("MOVAPS_M_X", "MOVAPS",
                      (op("mem", MEM, "w"), op("op1", VEC, "r")),
                      extension="SSE"))
    isa.add(InstrSpec("MOVAPS_X_M", "MOVAPS",
                      (op("op1", VEC, "w"), op("mem", MEM, "r")),
                      extension="SSE"))
    # --- excluded-by-the-algorithm classes (must exist to be excluded) ---
    isa.add(InstrSpec("NOP", "NOP", (), is_nop=True))
    isa.add(InstrSpec("PAUSE", "PAUSE", ()))
    isa.add(InstrSpec("LFENCE", "LFENCE", (), serializing=True))
    isa.add(InstrSpec("CPUID", "CPUID",
                      (op("op1", GPR, "rw", implicit=True),),
                      serializing=True, system=True))
    isa.add(InstrSpec("RDMSR", "RDMSR",
                      (op("op1", GPR, "w", implicit=True),), system=True))
    isa.add(InstrSpec("JMP_R64", "JMP", (op("op1", GPR, "r"),),
                      control_flow=True))
    return isa


TEST_ISA = build_test_isa()


# ---------------------------------------------------------------------------
# a paper-scale ISA (§6.1): TEST_ISA plus seeded mnemonic families × operand
# forms.  Only the operand kinds and special semantics TEST_ISA already has
# occur, so every simulator (and every plain reference of it) runs it as is.
# The hidden tables of a generated ISA are drawn in core/uarch.py
# (``generate_isa_and_uarch``), from each variant's ``unit`` below.
# ---------------------------------------------------------------------------

_GPR_WIDTHS = (8, 16, 32, 64)
_CONDITIONS = ("O", "NO", "B", "AE", "E", "NE", "BE", "A", "S", "NS", "P",
              "NP", "L", "GE", "LE", "G")

# what a generated ISA holds at least once (given room): operand forms in
# §6.1's terms, then special semantics, then the excluded classes
FORM_TAGS = tuple(f"gpr_{f}{w}" for f in ("rr", "ri") for w in _GPR_WIDTHS) + (
    "mem_src", "mem_dst", "sse_xx", "avx_xxx", "implicit")
SEMANTIC_TAGS = ("flags_read", "flags_written", "divider", "zero_idiom",
                 "eliminable", "same_reg")
EXCLUDED_TAGS = ("nop", "serializing", "system", "control_flow")


@dataclass(frozen=True)
class Family:
    """A mnemonic and the operand forms it comes in.

    ``dest`` is op1's mode, ``flags`` the status flags' (``""``: none),
    ``unit`` the class of execution unit its hidden μops are drawn from."""
    mnemonic: str
    unit: str
    forms: tuple
    dest: str = "rw"
    flags: str = "w"
    zero_idiom: bool = False
    eliminable: tuple = ()     # forms whose reg-reg variant is eliminable


def _fam(names, unit, forms, **kw) -> list:
    return [Family(n, unit, tuple(forms.split()), **kw) for n in names.split()]


def _gpr_forms(kinds: str, widths=_GPR_WIDTHS) -> str:
    return " ".join(f"{k}/{w}" for k in kinds.split() for w in widths)


_VEC = "xx xm vxxx vxxm vyyy"
_VEC_I = "xxi vxxxi"

FAMILIES: tuple = tuple(
    _fam("ADD SUB AND OR", "alu", _gpr_forms("rr ri rm mr"))
    + _fam("XOR", "alu", _gpr_forms("rr ri rm mr"), zero_idiom=True)
    + _fam("ADC SBB", "flagsuse", _gpr_forms("rr ri rm mr"), flags="rw")
    + _fam("CMP TEST", "alu", _gpr_forms("rr ri rm mr"), dest="r")
    + _fam("IMUL", "mul", _gpr_forms("rr rm", (16, 32, 64)))
    + _fam("IMUL3", "mul", _gpr_forms("rri8", (16, 32, 64)), dest="w")
    + _fam("INC DEC NEG", "alu", _gpr_forms("r m"))
    + _fam("NOT", "alu", _gpr_forms("r m"), flags="")
    + _fam("SHL SHR SAR ROL ROR RCL RCR", "shift", _gpr_forms("ri8 mi8"),
           flags="rw")
    + _fam("POPCNT LZCNT TZCNT BSF BSR", "bitcnt",
           _gpr_forms("rr rm", (16, 32, 64)), dest="w")
    + _fam("BT", "shift", _gpr_forms("rr ri8", (16, 32, 64)), dest="r")
    + _fam("BTS BTR BTC", "shift", _gpr_forms("rr ri8", (16, 32, 64)))
    + _fam("SHLD SHRD", "samereg", _gpr_forms("rri8", (16, 32, 64)))
    + _fam("MOV", "move", _gpr_forms("rr ri rm mr"), dest="w", flags="",
           eliminable=("rr/32", "rr/64"))
    + _fam("MOVZX MOVSX", "move", "zx/16_8 zx/32_8 zx/32_16 zx/64_8 "
           "mzx/32_8 mzx/32_16", dest="w", flags="",
           eliminable=("zx/32_8", "zx/32_16"))
    + [f for c in _CONDITIONS for f in _fam(
        f"CMOV{c}", "flagsuse", _gpr_forms("rr rm", (16, 32, 64)),
        flags="r")]
    + [f for c in _CONDITIONS for f in _fam(
        f"SET{c}", "flagsuse", "r/8 m/8", dest="w", flags="r")]
    + _fam("ANDN BEXTR BZHI", "alu", _gpr_forms("rrr", (32, 64)), dest="w")
    + _fam("PDEP PEXT", "bitcnt", _gpr_forms("rrr", (32, 64)), dest="w",
           flags="")
    + _fam("SARX SHLX SHRX", "shift", _gpr_forms("rrr", (32, 64)),
           dest="w", flags="")
    + _fam("RORX", "shift", _gpr_forms("rri8", (32, 64)), dest="w", flags="")
    + _fam("BLSI BLSR BLSMSK", "alu", _gpr_forms("rr", (32, 64)), dest="w")
    + _fam("LEA", "lea", "lea/16 lea/32", dest="w", flags="")
    + _fam("LEA3", "mul", _gpr_forms("rrr", (32, 64)), dest="w", flags="")
    + _fam("XCHG", "alu", _gpr_forms("xchg"), flags="")
    + _fam("MUL IMUL1", "mul", _gpr_forms("hi hm", (16, 32, 64)))
    + _fam("DIV IDIV", "div", _gpr_forms("div mdiv"))
    + _fam("CWD CDQ CQO", "alu", "sign", flags="")
    + _fam("CBW CWDE CDQE", "alu", "ext", flags="")
    + _fam("LAHF", "flagsuse", "lahf", dest="w", flags="r")
    + _fam("CLC STC", "alu", "flagsonly")
    # --- vector integer (SSE, with AVX forms) ---
    + _fam("PADDB PADDW PADDQ PSUBB PSUBW PSUBD PSUBQ PADDSB PADDSW PADDUSB "
           "PADDUSW PSUBSB PSUBSW PSUBUSB PSUBUSW PAVGB PAVGW PMINUB PMINSW "
           "PMAXUB PMAXSW PMINSB PMINSD PMINUD PMAXSB PMAXSD PMAXUD PSIGNB "
           "PSIGNW PSIGND PANDN POR PCMPEQB PCMPEQW PCMPEQD PCMPEQQ",
           "vint", _VEC, flags="")
    + _fam("PXOR", "vint", _VEC, flags="", zero_idiom=True)
    + _fam("PCMPGTB PCMPGTW PCMPGTD", "vint", _VEC, flags="",
           zero_idiom=True)
    + _fam("PABSB PABSW PABSD", "vint", "xx xm vxx", dest="w", flags="")
    + _fam("PMULLW PMULHW PMULHUW PMULLD PMULUDQ PMULDQ PMADDWD PMADDUBSW "
           "PSADBW", "vmul", _VEC, flags="")
    + _fam("PUNPCKLBW PUNPCKHBW PUNPCKLWD PUNPCKHWD PUNPCKLDQ PUNPCKHDQ "
           "PUNPCKLQDQ PUNPCKHQDQ PACKSSWB PACKSSDW PACKUSWB PACKUSDW PHADDW "
           "PHADDD PHSUBW PHSUBD", "vshuf", _VEC, flags="")
    + _fam("PSLLW PSLLD PSLLQ PSRLW PSRLD PSRLQ PSRAW PSRAD", "vshift",
           _VEC, flags="")
    + _fam("PALIGNR PBLENDW", "vshuf", _VEC_I, flags="")
    + _fam("PSHUFLW PSHUFHW", "vshuf", _VEC_I, dest="w", flags="")
    + _fam("PTEST", "vint", "xx vxx", dest="r", flags="w")
    # --- vector floating point ---
    + _fam("ADDPS ADDPD ADDSS ADDSD SUBPS SUBPD SUBSS SUBSD MULPS MULPD "
           "MULSS MULSD MINPS MINPD MAXPS MAXPD HADDPS HADDPD ADDSUBPS "
           "ADDSUBPD", "vfp", _VEC, flags="")
    + _fam("ANDPS ANDPD ANDNPS ANDNPD ORPS ORPD", "vint", _VEC, flags="")
    + _fam("XORPS XORPD", "vint", _VEC, flags="", zero_idiom=True)
    + _fam("UNPCKLPS UNPCKHPS UNPCKLPD UNPCKHPD", "vshuf", _VEC, flags="")
    + _fam("SHUFPS SHUFPD BLENDPS BLENDPD CMPPS CMPPD", "vfp", _VEC_I,
           flags="")
    + _fam("CVTDQ2PS CVTPS2DQ CVTTPS2DQ CVTPD2PS CVTPS2PD RCPPS RSQRTPS "
           "ROUNDPS ROUNDPD", "vfp", "xx xm vxx", dest="w", flags="")
    + _fam("DIVPD DIVSS DIVSD SQRTPS SQRTPD SQRTSS SQRTSD", "vdiv",
           "xx xm vxxx vyyy", flags="")
    + _fam("MOVAPD MOVDQA MOVDQU MOVUPS MOVUPD", "vmove", "xx xm mx vxx",
           dest="w", flags="", eliminable=("xx", "vxx"))
    # --- crossing register types (never a plain two-operand mover, so the
    # latency chains' mover set stays TEST_ISA's) ---
    + _fam("PEXTRB PEXTRW PEXTRD", "cross", "rxi/32", dest="w", flags="")
    + _fam("PINSRB PINSRW PINSRD", "cross", "xri/32", flags="")
    + _fam("PINSRQ", "cross", "xri/64", flags="")
    + _fam("CVTSI2SS CVTSI2SD", "cross", "xr/32 xr/64", flags="")
)

_EXCLUDED = (
    [InstrSpec(f"NOP_{k}{w}", "NOP", (op("op1", GPR if k == "R" else MEM,
                                          "r", width=w),), is_nop=True)
     for k in "RM" for w in (16, 32)]
    + [InstrSpec(n, n, (), serializing=True)
       for n in ("MFENCE", "SFENCE", "SERIALIZE")]
    + [InstrSpec(n, n, (op("op1", GPR, "w", implicit=True),
                        op("hi", GPR, "w", implicit=True)), system=True)
       for n in ("RDTSC", "RDPMC", "XGETBV")]
    + [InstrSpec("RDTSCP", "RDTSCP", (op("op1", GPR, "w", implicit=True),
                                      op("hi", GPR, "w", implicit=True)),
                 serializing=True, system=True)]
    + [InstrSpec(n, n, (), system=True)
       for n in ("WRMSR", "XSETBV", "SWAPGS", "CLTS", "INVD", "WBINVD",
                 "HLT", "STI", "CLI")]
    + [InstrSpec(f"{n}_M", n, (op("mem", MEM, "r"),), system=True)
       for n in ("LGDT", "LIDT", "INVLPG")]
    + [InstrSpec(f"{n}_M", n, (op("mem", MEM, "w"),), system=True)
       for n in ("SGDT", "SIDT")]
    + [InstrSpec(f"J{c}_REL{w}", f"J{c}", (_Fr,), control_flow=True)
       for c in _CONDITIONS for w in (8, 32)]
    + [InstrSpec(f"JMP_REL{w}", "JMP", (), control_flow=True)
       for w in (8, 32)]
    + [InstrSpec("JMP_M64", "JMP", (op("mem", MEM, "r"),),
                 control_flow=True),
       InstrSpec("CALL_R64", "CALL", (op("op1", GPR, "r"),),
                 control_flow=True),
       InstrSpec("CALL_M64", "CALL", (op("mem", MEM, "r"),),
                 control_flow=True),
       InstrSpec("RET", "RET", (), control_flow=True),
       InstrSpec("RET_I16", "RET", (op("imm", IMM, "r"),),
                 control_flow=True)]
    + [InstrSpec(f"{n}_REL8", n, (op("op1", GPR, "rw", implicit=True),),
                 control_flow=True) for n in ("LOOP", "LOOPE", "LOOPNE")]
    + [InstrSpec("JRCXZ_REL8", "JRCXZ", (op("op1", GPR, "r", implicit=True),),
                 control_flow=True)]
    + [InstrSpec(n, n, (), control_flow=True, system=True)
       for n in ("SYSCALL", "SYSRET", "INT3", "UD2")]
    + [InstrSpec(n, n, (), control_flow=True, serializing=True, system=True)
       for n in ("IRETQ", "SYSENTER", "SYSEXIT")]
    + [InstrSpec("FNOP", "FNOP", (), is_nop=True),
       InstrSpec("NOP_X", "NOP", (op("op1", VEC, "r"),), is_nop=True,
                 extension="SSE")]
)


def _flags(mode: str) -> tuple:
    return {"": (), "w": (_F,), "rw": (_Frw,), "r": (_Fr,)}[mode]


def _variant(fam: Family, form: str) -> InstrSpec | None:
    """One variant of ``fam`` in ``form`` (``kind`` or ``kind/width``;
    ``None``: no such variant)."""
    mn, d = fam.mnemonic, fam.dest
    kind, _, wtxt = form.partition("/")
    w = int(wtxt) if wtxt.isdigit() else 64
    kw = {"zero_idiom": fam.zero_idiom and kind in ("rr", "xx", "vxxx",
                                                    "vyyy"),
          "may_eliminate": form in fam.eliminable}
    if kind in ("rri8", "rrr", "hi", "hm") and w == 8:
        return None
    g1 = op("op1", GPR, d, width=w)
    g2 = op("op2", GPR, "r", width=w)
    imm = op("imm", IMM, "r")
    mem = op("mem", MEM, "r")
    vex = "r" if d == "r" else "w"        # VEX forms do not read their dest
    forms = {
        "rr": (f"_R{w}_R{w}", (g1, g2)),
        "ri": (f"_R{w}_I{min(w, 32)}", (g1, imm)),
        "ri8": (f"_R{w}_I8", (g1, imm)),
        "rm": (f"_R{w}_M{w}", (g1, mem)),
        "mr": (f"_M{w}_R{w}", (op("mem", MEM, d), g2)),
        "mi8": (f"_M{w}_I8", (op("mem", MEM, d), imm)),
        "r": (f"_R{w}", (g1,)),
        "m": (f"_M{w}", (op("mem", MEM, d),)),
        "rri8": (f"_R{w}_R{w}_I8", (g1, g2, imm)),
        "rrr": (f"_R{w}_R{w}_R{w}", (g1, g2, op("op3", GPR, "r", width=w))),
        "lea": (f"_R{w}", (g1, op("op2", GPR, "r"))),
        "xchg": (f"_R{w}_R{w}", (op("op1", GPR, "rw", width=w),
                                 op("op2", GPR, "rw", width=w))),
        # one-operand multiply and divide: rDX is implicit
        "hi": (f"_R{w}", (op("op1", GPR, "rw", width=w), g2,
                          op("hi", GPR, "w", implicit=True, width=w))),
        "hm": (f"_M{w}", (op("op1", GPR, "rw", width=w), mem,
                          op("hi", GPR, "w", implicit=True, width=w))),
        "div": (f"_R{w}", (op("op1", GPR, "rw", width=w), g2,
                           op("hi", GPR, "rw", implicit=True, width=w))),
        "mdiv": (f"_M{w}", (op("op1", GPR, "rw", width=w), mem,
                            op("hi", GPR, "rw", implicit=True, width=w))),
        "xx": ("_X_X", (op("op1", VEC, d), op("op2", VEC, "r"))),
        "xxi": ("_X_X_I8", (op("op1", VEC, d), op("op2", VEC, "r"), imm)),
        "xm": ("_X_M", (op("op1", VEC, d), mem)),
        "mx": ("_M_X", (op("mem", MEM, "w"), op("op1", VEC, "r"))),
        "vxx": ("_X_X", (op("op1", VEC, d), op("op2", VEC, "r"))),
        "vxxx": ("_X_X_X", (op("op1", VEC, vex), op("op2", VEC, "r"),
                            op("op3", VEC, "r"))),
        "vxxxi": ("_X_X_X_I8", (op("op1", VEC, vex), op("op2", VEC, "r"),
                                op("op3", VEC, "r"), imm)),
        "vyyy": ("_Y_Y_Y", (op("op1", VEC, vex), op("op2", VEC, "r"),
                            op("op3", VEC, "r"))),
        "vxxm": ("_X_X_M", (op("op1", VEC, vex), op("op2", VEC, "r"), mem)),
        "rxi": (f"_R{w}_X_I8", (g1, op("op2", VEC, "r"), imm)),
        "xri": (f"_X_R{w}_I8", (op("op1", VEC, d), g2, imm)),
        "xr": (f"_X_R{w}", (op("op1", VEC, d), g2)),
    }
    if kind in ("zx", "mzx"):             # MOVZX/MOVSX: dest_src widths
        dw, sw = (int(x) for x in wtxt.split("_"))
        src = "M" if kind == "mzx" else "R"
        suffix = f"_R{dw}_{src}{sw}"
        ops = (op("op1", GPR, d, width=dw),
               mem if src == "M" else op("op2", GPR, "r", width=sw))
    elif kind == "sign":                  # CWD/CDQ/CQO: rDX from rAX
        w = {"CWD": 16, "CDQ": 32, "CQO": 64}[mn]
        suffix, ops = "", (op("op1", GPR, "r", implicit=True, width=w),
                           op("hi", GPR, "w", implicit=True, width=w))
    elif kind == "ext":                   # CBW/CWDE/CDQE: rAX in place
        w = {"CBW": 16, "CWDE": 32, "CDQE": 64}[mn]
        suffix, ops = "", (op("op1", GPR, "rw", implicit=True, width=w),)
    elif kind == "lahf":
        suffix, ops = "", (op("op1", GPR, "w", implicit=True, width=8),)
    elif kind == "flagsonly":
        suffix, ops = "", ()
    elif kind in forms:
        suffix, ops = forms[kind]
    else:
        raise ValueError(f"unknown operand form {form!r}")
    kw["uses_divider"] = kind in ("div", "mdiv") or fam.unit == "vdiv"
    avx = kind.startswith("v")
    ext = "BASE"
    if any(o.otype == VEC for o in ops):
        ext = "AVX" if avx else "SSE"
    mnemonic = {"IMUL3": "IMUL", "IMUL1": "IMUL", "LEA3": "LEA"}.get(mn, mn)
    mnemonic = f"V{mnemonic}" if avx else mnemonic
    return InstrSpec(mnemonic + suffix, mnemonic,
                     ops + _flags(fam.flags), extension=ext, **kw)


def variant_tags(spec: InstrSpec) -> frozenset:
    """The operand form, special semantics and excluded class of a variant,
    in the terms of ``FORM_TAGS``, ``SEMANTIC_TAGS`` and ``EXCLUDED_TAGS``."""
    tags = set()
    mem = [o for o in spec.operands if o.otype == MEM]
    ex = [o for o in spec.explicit_operands]
    if any(o.written for o in mem):
        tags.add("mem_dst")
    elif mem:
        tags.add("mem_src")
    if any(o.implicit and o.otype != FLAGS for o in spec.operands):
        tags.add("implicit")
    if ex and not mem and all(o.otype in (GPR, IMM) for o in ex):
        gprs = [o for o in ex if o.otype == GPR]
        imm = any(o.otype == IMM for o in ex)
        if len(gprs) == 2 and not imm and gprs[0].width == gprs[1].width:
            tags.add(f"gpr_rr{gprs[0].width}")
        elif len(gprs) == 1 and imm:
            tags.add(f"gpr_ri{gprs[0].width}")
    if ex and not mem and all(o.otype == VEC for o in ex):
        if spec.extension == "SSE" and len(ex) == 2:
            tags.add("sse_xx")
        elif spec.extension == "AVX" and len(ex) == 3:
            tags.add("avx_xxx")
    tags.update(t for t, on in (
        ("flags_read", spec.reads_flags()),
        ("flags_written", spec.writes_flags()),
        ("divider", spec.uses_divider), ("zero_idiom", spec.zero_idiom),
        ("eliminable", spec.may_eliminate),
        ("same_reg", _units().get(spec.mnemonic) == "samereg"),
        ("nop", spec.is_nop), ("serializing", spec.serializing),
        ("system", spec.system), ("control_flow", spec.control_flow)) if on)
    return frozenset(tags)


def _stratified(pool: list, tags: tuple, k: int) -> list:
    """``k`` of the shuffled ``pool``: first one variant for each tag that
    some variant carries (in ``tags`` order), then the rest in order."""
    picked, seen = [], set()
    for t in tags:
        if len(picked) >= k:
            break
        for i, s in enumerate(pool):
            if i not in seen and t in variant_tags(s):
                picked.append(s)
                seen.add(i)
                break
    rest = (s for i, s in enumerate(pool) if i not in seen)
    return picked + [next(rest) for _ in range(k - len(picked))]


# §5.1.1's assumption for the AVX blocking set: TEST_ISA's only 1-μop
# port-0 vector instructions are SSE, so every generated ISA has this one
ANCHORS = (Family("AESDEC", "aes", ("vxxx",), flags=""),)


@functools.cache
def _units() -> dict:
    """mnemonic -> unit class (the first family that builds it)."""
    out: dict = {}
    for fam in ANCHORS + FAMILIES:
        for form in fam.forms:
            v = _variant(fam, form)
            if v is not None:
                out.setdefault(v.mnemonic, fam.unit)
    return out


def variant_unit(spec: InstrSpec) -> str:
    """The execution-unit class a generated variant's hidden μops are drawn
    from (``excluded`` for the classes the tool leaves out)."""
    return _units()[spec.mnemonic] if spec.characterized else "excluded"


def generate_isa(seed: int, n_variants: int) -> ISA:
    """All of TEST_ISA plus ``n_variants - len(TEST_ISA)`` variants drawn
    from ``FAMILIES`` × their operand forms, keeping TEST_ISA's share of
    excluded classes (NOP, serializing, system, control flow).  Every tag
    of ``FORM_TAGS``, ``SEMANTIC_TAGS`` and ``EXCLUDED_TAGS`` is present
    when the count leaves room.  Deterministic in ``seed``."""
    base = list(TEST_ISA)
    k = n_variants - len(base)
    if k < len(ANCHORS):
        raise ValueError(f"a generated ISA holds TEST_ISA's {len(base)} "
                         f"variants and {len(ANCHORS)} more; asked for "
                         f"{n_variants}")
    pool = [s for s in (_variant(f, form) for f in FAMILIES
                        for form in f.forms)
            if s is not None and s.name not in TEST_ISA]
    excl = [s for s in _EXCLUDED if s.name not in TEST_ISA]
    share = sum(not s.characterized for s in base) / len(base)
    k_ex = round(k * share)
    if k - k_ex > len(pool) or k_ex > len(excl):
        raise ValueError(f"the families hold {len(pool) + len(excl)} "
                         f"variants beyond TEST_ISA; asked for {k}")
    rng = random.Random(f"generate_isa/{seed}")
    rng.shuffle(pool)
    rng.shuffle(excl)
    anchors = [_variant(f, f.forms[0]) for f in ANCHORS]
    drawn = anchors + (
        _stratified(pool, FORM_TAGS + SEMANTIC_TAGS,
                    k - k_ex - len(anchors))
        + _stratified(excl, EXCLUDED_TAGS, k_ex))
    return ISA(base + drawn)
