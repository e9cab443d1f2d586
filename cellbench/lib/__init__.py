"""The yardstick: copies and arithmetic that later PRs may not change."""
