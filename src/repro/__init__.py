"""SPEC-RL reproduction package."""
