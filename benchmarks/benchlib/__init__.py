"""The benchmark's own code: the yardstick later PRs may not change."""
