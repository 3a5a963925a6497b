"""Static analysis for the repro tree: plan auditor + repo lint.

Two passes, one CI entry point (``python -m repro.analysis --strict``):

* :mod:`repro.analysis.audit` — enumerate a lattice of ``FFTSpec`` /
  ``GEMMSpec`` configurations over the host-device meshes, lower each
  cached plan executor to post-partitioning HLO *without executing data*,
  and diff the parsed collectives (:mod:`repro.analysis.hlo`) against the
  analytic volume models. Any count/byte/psum-width mismatch, unexpected
  all-gather, or dtype downcast between spec and root signature fails.
* :mod:`repro.analysis.lint` — AST rules L001..L005 for repo-specific
  contracts (deprecated FFT kwargs, raw ``jnp.fft`` outside core/fft,
  assert-as-input-validation, unlocked mesh dispatch, frozen-field
  mutation), gated strict-on-new by a checked-in baseline.

:mod:`repro.analysis.hlo` is import-light (stdlib ``re`` only), so the
audit, the benchmarks and the tests share one collective parser without
import-order traps.
"""
from repro.analysis.hlo import (CollectiveOp, parse_collectives,
                                root_signature, summarize)

__all__ = ["CollectiveOp", "parse_collectives", "root_signature",
           "summarize"]
