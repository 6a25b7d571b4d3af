"""``calibrate.py`` with the routed-expert faults of ``lib/faults_moe.py``
plantable as well: the same readings (program, bf16 control, a fault) over
many seeds in one process.

    python bench/calibrate_moe.py --workload lfm2moe.quantize --seconds <s> \\
        --seeds 1,2,3 --control-seeds 4,5 --fault capacity --fault-seeds 6,7 \\
        [--out file.jsonl]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import calibrate  # noqa: E402
from lib import faults, faults_moe  # noqa: E402

if __name__ == "__main__":
    faults.FAULTS.update(faults_moe.FAULTS)
    sys.exit(calibrate.main())
