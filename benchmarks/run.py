"""Benchmark aggregator: one section per paper table/figure + the roofline
readers.  ``python -m benchmarks.run [--quick]``."""

from __future__ import annotations

import argparse
import sys
import time


def _section(title: str):
    print(f"\n### {title}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes, fewer reps")
    args = ap.parse_args(argv)

    from benchmarks import (fig4_1d_layouts, fig6_2d, fig7_4d, fig8_10d,
                            fig9_dims, kernel_roofline, speedup_table)
    from benchmarks.common import emit_csv

    t0 = time.time()
    _section("fig4_1d_layouts (paper Fig. 4)")
    rows = fig4_1d_layouts.run(levels=(10, 14, 18) if args.quick
                               else (10, 14, 18, 20, 22))
    print(emit_csv(rows))

    _section("fig6_2d measured-vs-calculated (paper Fig. 5/6)")
    rows = fig6_2d.run(level_pairs=((6, 6), (9, 9)) if args.quick else
                       ((6, 6), (8, 8), (10, 10), (11, 11), (12, 10)))
    print(emit_csv(rows))

    _section("fig7_4d (paper Fig. 7)")
    rows = fig7_4d.run(levels_list=((4, 4, 4, 4), (5, 5, 5, 5)) if args.quick
                       else ((4, 4, 4, 4), (5, 5, 5, 5), (6, 6, 6, 6),
                             (7, 6, 6, 6)))
    print(emit_csv(rows))

    _section("fig8_10d anisotropic + reduced-op ablation (paper Fig. 8)")
    rows = fig8_10d.run(l1_values=(6, 10) if args.quick else
                        (6, 8, 10, 12, 14))
    print(emit_csv(rows))

    _section("fig9_dims (paper Fig. 9)")
    print(emit_csv(fig9_dims.run()))

    _section("speedup table (paper Sect. 5 headline)")
    print(emit_csv(speedup_table.run()))

    _section("kernel roofline projection (TPU v5e)")
    kernel_roofline.main()

    _section("arch x shape roofline (from dry-run artifacts)")
    from benchmarks import roofline
    roofline.main(["--mesh", "single"])

    print(f"\n# total bench time: {time.time() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
