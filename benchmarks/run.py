"""Benchmark harness entry point: ``python -m benchmarks.run``.

One module per paper table/figure (see DESIGN.md §7):
  bench_profiles        Table 1 + composition check
  bench_cloud           Figure 4 (cloud, 6 panels + ratios)
  bench_mobile          Figure 5 (mobile, 6 panels + ratios)
  bench_tco             §5.1 3-year TCO/QPS
  bench_long_generation §5.1 1000/1000 + mobile battery scaling
  bench_roofline        §Roofline table from the dry-run artifacts
  bench_serving         engine batching: aligned vs ragged, disp/step
"""
from __future__ import annotations

import sys
import time


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_cloud, bench_long_generation,
                            bench_mobile, bench_profiles, bench_roofline,
                            bench_serving, bench_tco)
    benches = {
        "profiles": bench_profiles.run,
        "cloud": bench_cloud.run,
        "mobile": bench_mobile.run,
        "tco": bench_tco.run,
        "long_generation": bench_long_generation.run,
        "roofline": bench_roofline.run,
        "serving": bench_serving.run,
    }
    names = (argv if argv is not None else sys.argv[1:]) or list(benches)
    for name in names:
        t0 = time.time()
        benches[name]()
        print(f"\n[{name} done in {time.time() - t0:.1f}s]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
