"""Runners, found by the ``runner`` key of a configuration: ``train`` drives
a training step in a plain loop, ``serve`` drives a serving engine under a
traffic mix."""
