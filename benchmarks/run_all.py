"""Run the BASELINE configs that have a bench here; one driver JSON line
each (config 4, the Transformer, is ``perf/run.py``'s)."""

from __future__ import annotations


def main():
    from . import (bench_frcnn, bench_lenet, bench_module, bench_resnet50,
                   bench_ssd)

    bench_lenet.main()
    bench_resnet50.main()
    import bench as bench_bert  # repo-root bench.py = config 3

    bench_bert.main()
    bench_ssd.main()
    bench_frcnn.main()
    bench_module.main()


if __name__ == "__main__":
    main()
