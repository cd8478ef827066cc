"""The chip benchmark's harness: deployments, traffic, trace reduction,
plain references and the metric readers' shared record.

Nothing here is imported by the program under test, and nothing here
imports the program's own measurement code (`chip_smoke.py`,
`benchmarks/*.py`): the yardstick stays fixed while the program changes.
"""
