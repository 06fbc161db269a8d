"""Where tier 1's time went: `python tools/tier1_times.py [junit.xml]`.

Reads the junit file the driver's tier-1 command writes (`/tmp/_t1.xml`,
`/root/TESTS_LAST_RUN.json` has the command) and prints seconds and cases by
file, the twenty costliest tests, the sum, the sum over six workers, and every
file over 100 s: under `--dist loadfile` a file is one worker's, so a file
over 200 s is a chain the other five wait for and is split, and the files
over 100 s are handed out first, in this order (`tests/conftest.py`,
`LONGEST_FIRST`; CLAUDE.md, tier-1 BUDGET).
"""
import collections
import sys
import xml.etree.ElementTree as ET

path = sys.argv[1] if len(sys.argv) > 1 else "/tmp/_t1.xml"
cases = [(float(c.get("time", 0)), c.get("classname", "").split(".Test")[0].replace(".", "/") + ".py", c.get("name"))
         for c in ET.parse(path).iter("testcase")]
by_file = collections.defaultdict(lambda: [0.0, 0])
for seconds, file, _ in cases:
    by_file[file][0] += seconds
    by_file[file][1] += 1
total = sum(s for s, _ in by_file.values())
ranked = sorted(by_file.items(), key=lambda kv: -kv[1][0])
print(f"{'seconds':>8} {'cases':>5}  file")
for file, (seconds, n) in ranked:
    print(f"{seconds:8.1f} {n:5d}  {file}")
print("\nthe twenty costliest tests")
for seconds, file, name in sorted(cases, reverse=True)[:20]:
    print(f"{seconds:8.1f}  {file}::{name}")
print(f"\n{len(cases)} cases in {len(by_file)} files, {total:.0f} CPU-seconds, {total / 6:.0f} s over six workers")
for file, (seconds, _) in ranked:
    if seconds > 100:
        print(f"over {'200 s, split it' if seconds > 200 else '100 s, first'}"
              f": {file} ({seconds:.0f} s)")
