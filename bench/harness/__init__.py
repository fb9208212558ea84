"""The benchmark's yardstick: configurations, traffic, the plain reference,
the trace reduction and the peaks table.  Nothing here is imported by the
program under test, and the reference imports nothing of the program."""
