"""The benchmark's own machinery: finding a cell's files, the run's
window and clock, the trace reduction and the peaks table.  Nothing here
imports the system under test; the app adapters under ``bench/apps`` do."""
