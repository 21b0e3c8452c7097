"""host-transfer: no callbacks or host round-trips in serve-path jaxprs.

The runtime ``jax.transfer_guard("disallow")`` is the dynamic defence
against device<->host syncs — but it is blind on the CPU backend, where
D2H is zero-copy and unguarded, which is exactly where CI runs.  This
pass is the static complement:

* **callback primitives** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` / ``debug_print`` anywhere in
  the nested jaxpr mean the compiled computation re-enters Python per
  dispatch: a synchronisation point and a TPU-incompatibility on the
  serve path.  Flagged wherever they hide (cond branches, scan bodies,
  shard_map bodies).
* **host-constant round-trips** — large raw ``np.ndarray`` consts closed
  over by the jaxpr are re-uploaded host->device copies baked into the
  trace.  Device-resident ``jax.Array`` params (the normal closure
  pattern for model weights) are NOT flagged — only plain numpy buffers
  above a size threshold, which indicate catalogue-sized data taking the
  host path on every trace.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.analysis.core import (AnalysisPass, EntryContext, Finding,
                                 SEV_ERROR, iter_eqns)

CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
})

#: raw-numpy consts smaller than this ride the trace for free (scalars,
#: tiny index vectors); bigger ones are a per-trace host->device upload.
DEFAULT_CONST_BYTES_LIMIT = 1 << 20


class HostTransferPass(AnalysisPass):
    name = "host-transfer"
    description = ("no callback primitives and no oversized raw-numpy "
                   "host constants anywhere in the serve-path jaxpr")
    scope = "entrypoint"
    requires_trace = True

    def __init__(self, const_bytes_limit: int = DEFAULT_CONST_BYTES_LIMIT):
        self.const_bytes_limit = const_bytes_limit

    def run(self, entrypoint: str, built: Any, ctx: Optional[EntryContext]
            ) -> Tuple[List[Finding], Dict[str, Any]]:
        findings: List[Finding] = []
        jaxpr = ctx.trace()

        n_callbacks = 0
        for eqn, path in iter_eqns(jaxpr):
            if eqn.primitive.name in CALLBACK_PRIMITIVES:
                n_callbacks += 1
                cb = eqn.params.get("callback")
                findings.append(Finding(
                    self.name, entrypoint, SEV_ERROR, "host-callback",
                    f"'{eqn.primitive.name}' primitive on the serve path "
                    f"(at {'/'.join(path) or '<top>'}): compiled dispatch "
                    f"re-enters Python per batch",
                    details={"primitive": eqn.primitive.name,
                             "path": list(path),
                             "callback": repr(cb) if cb is not None
                             else None}))

        n_big_consts = 0
        for c in jaxpr.consts:
            c = getattr(c, "val", c)   # traced host consts wrap a numpy array
            if isinstance(c, np.ndarray) and c.nbytes > self.const_bytes_limit:
                n_big_consts += 1
                findings.append(Finding(
                    self.name, entrypoint, SEV_ERROR, "host-constant",
                    f"raw numpy const of {c.nbytes} bytes "
                    f"(shape {c.shape}, {c.dtype}) closed over by the "
                    f"trace: host->device round-trip on every dispatch — "
                    f"move it to device (jnp.asarray at build time)",
                    details={"nbytes": int(c.nbytes),
                             "shape": list(c.shape),
                             "dtype": str(c.dtype)}))

        return findings, {"n_callbacks": n_callbacks,
                          "n_big_host_consts": n_big_consts,
                          "n_consts": len(jaxpr.consts)}
