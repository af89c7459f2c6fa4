"""Extended fuzz soak: higher seeds than the suite's fixed range. Exact
equivalence bar is the same as tests/test_fuzz_equivalence.

Usage: python tools/fuzz_soak.py [first_seed] [n_seeds]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))

# CPU + 8 virtual devices, same as tests/conftest.py
os.environ.setdefault(
    "XLA_FLAGS",
    (os.environ.get("XLA_FLAGS", "")
     + " --xla_force_host_platform_device_count=8").strip())
import jax  # noqa: E402
jax.config.update("jax_platforms", "cpu")


def main():
    first = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    count = int(sys.argv[2]) if len(sys.argv) > 2 else 10
    from test_fuzz_equivalence import gen_config  # noqa: E402
    from test_dist_model_parallel import check_equivalence  # noqa: E402

    failures = 0
    for seed in range(first, first + count):
        specs, table_map, kw = gen_config(seed)
        try:
            check_equivalence(specs, input_table_map=table_map, **kw)
            print(f"seed {seed} OK", flush=True)
        except ValueError as e:
            # planner's legitimate unrunnable-config rejection (too few
            # tables for the device count after slicing — same contract as
            # the reference's empty-rank error, dist_model_parallel:799)
            if "Not enough tables" in str(e):
                print(f"seed {seed} SKIP (unrunnable config): {e}",
                      flush=True)
            else:
                failures += 1
                print(f"seed {seed} FAIL: {str(e)[:500]}", flush=True)
        except Exception as e:  # noqa: BLE001 - report and continue
            failures += 1
            print(f"seed {seed} FAIL: {str(e)[:500]}", flush=True)
    print(f"{'PASS' if failures == 0 else 'FAIL'}: "
          f"{count - failures}/{count} seeds OK", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
