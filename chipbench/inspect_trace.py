#!/usr/bin/env python3
"""Print the planes, lines and first events of the newest trace a
``--trace 1`` run left under chipbench/.trace (or of the .xplane.pb
given): what to read by hand before trusting xplane.py's reduction."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from chipbench import harness, xplane
    path = sys.argv[1] if len(sys.argv) > 1 else \
        xplane.newest_trace(harness.TRACE_DIR)
    print(path, os.path.getsize(path), "bytes")
    print("\n".join(xplane.describe(xplane.load(path), events_per_line=8)))
