"""The paper's evaluation substrate (arXiv:2105.03221 section 3, Fig. 1):
IoT devices in zones behind ONU APs, one OLT, an access-fog node behind
low-end gear, a metro router/switch with a metro-fog node, and a two-node
IP/WDM core to the cloud datacenter.  Node order and wiring follow the
program's ``paper_topology`` preset, so both sides route alike."""


def build(n_iot: int = 20, n_zones: int = 4):
    """Returns (processing nodes, network nodes, edges); a processing node
    is (name, hardware class, layer, overrides), a network node (name,
    hardware class)."""
    proc = [(f"iot{i}", "IOT_RPI4", "iot", {}) for i in range(n_iot)]
    proc += [("af0", "AF_I5", "af", {}), ("mf0", "MF_I5", "mf", {}),
             ("cdc0", "CDC_XEON", "cdc", {})]
    net = [(f"onu{z}", "ONU_AP") for z in range(n_zones)]
    net += [("olt0", "OLT"), ("mrouter0", "METRO_ROUTER"),
            ("mswitch0", "METRO_SWITCH"), ("core0", "IPWDM_NODE"),
            ("core1", "IPWDM_NODE"), ("af_router0", "LOW_END_ROUTER"),
            ("af_switch0", "LOW_END_SWITCH"), ("mf_router0", "LOW_END_ROUTER"),
            ("mf_switch0", "LOW_END_SWITCH")]
    edges = [(f"iot{i}", f"onu{i % n_zones}") for i in range(n_iot)]
    edges += [(f"onu{z}", "olt0") for z in range(n_zones)]
    edges += [("olt0", "af_router0"), ("af_router0", "af_switch0"),
              ("af_switch0", "af0"), ("olt0", "mrouter0"),
              ("mrouter0", "mswitch0"), ("mswitch0", "mf_router0"),
              ("mf_router0", "mf_switch0"), ("mf_switch0", "mf0"),
              ("mswitch0", "core0"), ("core0", "core1"), ("cdc0", "core1")]
    return proc, net, edges
