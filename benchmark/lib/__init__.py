"""The benchmark's own code: the yardstick later PRs cannot change."""
