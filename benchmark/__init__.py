"""The benchmark of record (BENCHMARK.json): harness, cells' data files,
plain references, trace reduction. Later PRs add files here, never edit one."""
