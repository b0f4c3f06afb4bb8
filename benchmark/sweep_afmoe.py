"""``sweep.py``'s ladder of rates for a cell of the AFMoE family.

    python benchmark/sweep_afmoe.py \
        --workload trinity-large-preview.serve-longctx-r80 \
        --rates 0.6,0.8,1.0,1.2,1.4 --seconds 40 --seed 5 --preroll 30

``sweep.py`` builds its server through ``program.build_serve`` (GPT-2;
``sweep_lfm2.py`` and ``sweep_sarvam_mla.py`` put their families'
there) and may not be edited; the ladder itself is model-agnostic. So
this puts the family's own ``build_serve`` in that one place and runs
``sweep.main`` as it stands: the same rungs, rows and ``sweep.json``.
"""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import program  # noqa: E402
import program_afmoe  # noqa: E402
import run as harness  # noqa: E402
import sweep  # noqa: E402


def main() -> None:
    def build_serve(cfg: dict, serving_block: dict, seed: int):
        # the full layers' tables' width comes from the mix
        # (jobs/serve_afmoe.py): found again from the cell, since
        # sweep hands over no more
        name = sys.argv[sys.argv.index("--workload") + 1]
        root = Path(sys.argv[sys.argv.index("--root") + 1]) \
            if "--root" in sys.argv else harness.ROOT
        traffic = harness.resolve(name, root)[3]
        return program_afmoe.build_serve(cfg, serving_block, seed,
                                         traffic["max_positions"])

    program.build_serve = build_serve
    sweep.main()


if __name__ == "__main__":
    main()
