#!/usr/bin/env python
"""obsctl — post-hoc forensic tooling over a run's observability artifacts.

Thin launcher around `tpu_dp.obs.obsctl` so the tool runs from a checkout
without installing the package:

    tools/obsctl.py timeline <run_dir>            # merged event stream
    tools/obsctl.py timeline <run_dir> --steps    # + per-step coverage
    tools/obsctl.py stragglers <run_dir>          # leave-one-out attribution
    tools/obsctl.py merge-trace <run_dir> -o t.json
    tools/obsctl.py diff <run_dir> --baseline BENCH_r02.json
    tools/obsctl.py diff <run_dir> --write-baseline base.json

Equivalent to ``python -m tpu_dp.obs``. Exit 0 clean / 1 regression
(diff) / 2 usage or artifact error. Needs no accelerator — postmortems
run anywhere the artifacts are readable.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu_dp.obs.obsctl import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
