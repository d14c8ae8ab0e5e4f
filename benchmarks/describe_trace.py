"""Print what a profiler trace holds: planes, lines and, line by line, the
event names that took most time. Look at one trace of each kind by hand
before trusting a reader's pattern (PERF.md, Layers).

    python3 benchmarks/describe_trace.py <trace directory>
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks import xplane
    print(json.dumps(xplane.describe(xplane.find(sys.argv[1])), indent=1))
