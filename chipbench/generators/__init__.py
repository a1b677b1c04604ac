"""chipbench.generators: seeded traffic.  One general generator per kind
of traffic; a traffic mix is a data file of its parameters."""
