"""The rule catalogue: every invariant the static checker enforces.

Each rule has a stable ID (``W*`` wire contracts, ``C*`` compiled-chunk
hygiene, ``D*`` donation, ``P*`` PRNG discipline, ``R*`` recompilation,
``A*`` AST / registry lint) so seeded-violation tests, waivers, and CI
reports all speak the same vocabulary.  A :class:`Violation` pins the rule
to a source location (file:line for AST rules, the traced combo for jaxpr
rules) — the checker's whole point is failing at *review* time with a
pointer, instead of after a multi-minute bitwise test sweep.

Waiving a rule (see README "Static analysis"):

  - CLI: ``python -m repro.analysis.check --all --waive A002`` drops every
    finding of that rule from the gate (still listed in the JSON report,
    flagged ``waived``);
  - inline (AST rules only): a ``# analysis: waive=A002`` comment on the
    offending line suppresses that single finding at the source.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

RULES = {
    # -- Layer 1: jaxpr auditor --------------------------------------------
    "W001": "payload_specs must equal the shapes/dtypes the uplink/downlink "
            "codecs actually see in the assembled round step (eval_shape "
            "cross-check; proves CommProfile wire-byte accounting honest)",
    "W002": "model_sync_specs must equal the shapes/dtypes the model-sync "
            "codecs see inside make_wire_aggregate",
    "W003": "a method's declared wire_channels must match the channels its "
            "traced round step actually crosses",
    "C001": "no host callbacks (pure_callback / io_callback / "
            "debug_callback / debug_print) inside the donated lax.scan "
            "chunk body",
    "C002": "no float64 values anywhere in the compiled chunk jaxpr",
    "D001": "donation must hold: every donated chunk-carry leaf is aliased "
            "into an output buffer (no silent copy)",
    "P001": "PRNG streams must be pairwise disjoint across the transport's "
            "uplink / downlink / model-sync channels and upload units",
    "F001": "the fault-injection retransmission/corruption PRNG stream "
            "(repro.faults.retry_key) must be disjoint from every "
            "CHANNEL_SALTS coded-channel stream and internally collision-"
            "free — a collision would couple simulated wire damage to a "
            "stochastic codec's rounding draws",
    "R001": "the chunk jaxpr's structural fingerprint must be identical "
            "across independent constructions (recompilation guard)",
    "T001": "telemetry is observation-only: the donated chunk program must "
            "be structurally identical with the recorder enabled vs "
            "disabled and contain no host callbacks — enabling "
            "observability may never retrace, recompile, or perturb the "
            "trained numerics (also an AST rule: no repro.telemetry "
            "imports or .telemetry access in methods/kernels)",
    # -- Layer 2: AST / registry lint --------------------------------------
    "A001": "no imports of the retired repro.core.protocol / "
            "repro.core.baselines shims",
    "A002": "no Python if/while on traced (jnp/lax) values in methods or "
            "kernels — use lax.cond / lax.select / jnp.where",
    "A003": "registry completeness: every registered FSLMethod defines "
            "make_async_hooks, agg_keys, wire_channels, and a consistent "
            "unit decomposition",
}


@dataclasses.dataclass(frozen=True)
class Violation:
    """One rule firing at one location."""

    rule: str                      # a RULES key
    message: str                   # what exactly is wrong
    file: Optional[str] = None     # source file (AST / registry rules)
    line: Optional[int] = None     # 1-based line (AST rules)
    combo: Optional[str] = None    # "method=cse_fsl codec=int8 ..." (jaxpr)
    waived: bool = False

    def where(self) -> str:
        if self.file is not None:
            loc = self.file if self.line is None else \
                f"{self.file}:{self.line}"
        else:
            loc = self.combo or "<global>"
        return loc

    def __str__(self):
        tag = " [waived]" if self.waived else ""
        return f"{self.rule}{tag} @ {self.where()}: {self.message}"

    def as_dict(self) -> dict:
        return {"rule": self.rule, "message": self.message,
                "file": self.file, "line": self.line, "combo": self.combo,
                "waived": self.waived}


def apply_waivers(violations, waive=()):
    """Mark (not drop) violations of waived rules; the gate counts only
    un-waived ones, the report keeps everything."""
    waive = set(waive)
    return [dataclasses.replace(v, waived=True) if v.rule in waive else v
            for v in violations]
