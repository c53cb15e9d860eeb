"""The benchmark of presto-tpu: the yardstick later PRs are held to."""
