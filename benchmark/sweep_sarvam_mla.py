"""``sweep.py``'s ladder of rates for a cell of the latent-attention
(``sarvam_mla``) family.

    python benchmark/sweep_sarvam_mla.py \
        --workload sarvam-105b.serve-longdoc-r80 \
        --rates 0.5,1,1.5,2,2.5,3 --seconds 40 --seed 5 --preroll 40

``sweep.py`` builds its server through ``program.build_serve`` (GPT-2;
``sweep_lfm2.py`` puts LFM2's there) and may not be edited; the ladder
itself is model-agnostic. So this puts the family's own
``build_serve`` in that one place and runs ``sweep.main`` as it
stands: the same rungs, rows and ``sweep.json``.
"""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import program  # noqa: E402
import program_sarvam_mla  # noqa: E402
import run as harness  # noqa: E402
import sweep  # noqa: E402


def main() -> None:
    def build_serve(cfg: dict, serving_block: dict, seed: int):
        # the tables' width comes from the mix (jobs/serve_sarvam_mla.py):
        # found again from the cell, since sweep hands over no more
        name = sys.argv[sys.argv.index("--workload") + 1]
        root = Path(sys.argv[sys.argv.index("--root") + 1]) \
            if "--root" in sys.argv else harness.ROOT
        traffic = harness.resolve(name, root)[3]
        return program_sarvam_mla.build_serve(cfg, serving_block, seed,
                                        traffic["max_positions"])

    program.build_serve = build_serve
    sweep.main()


if __name__ == "__main__":
    main()
