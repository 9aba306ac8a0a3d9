"""Static analysis of the checker pipeline (graftlint).

`jepsen_tpu.tpu.lint` holds the rule suite over traced kernels;
this package holds the drivers:

  registry.py     enumerates every compiled entry point and traces it
                  abstractly at representative shape buckets
  concurrency.py  AST lock-discipline lint over the threaded harness
                  modules (the _guarded_by_lock convention)
  driver.py       runs registry x rules + the concurrency lint,
                  aggregates, renders, and gates against the
                  committed lint-baseline.json

Surfaced via `python -m jepsen_tpu lint`, the web /lint page, and
`lint.*` telemetry counters. doc/static-analysis.md is the rule
catalog.
"""

from .driver import LintReport, run_lint  # noqa: F401
