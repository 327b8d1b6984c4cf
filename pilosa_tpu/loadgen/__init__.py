"""The Star Schema Benchmark star-schema generator and its numpy oracle
(``ssb.py``): seeded lineorder + date / customer / supplier / part
tables, the 13 queries Q1.1-Q4.3 as SQL, and an independent reference
for every answer."""
