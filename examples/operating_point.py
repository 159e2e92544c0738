"""One operating point, everything derived: the ISSUE 5 fan-out demo.

A single ``core.hw.OperatingPoint`` — (backend, dataflow, bits, data
rate) — is the only hardware knob you set.  Everything else follows from
the paper's own solvers:

  * DPE size N        <- scalability analysis (Eqs. 1-3, Fig. 9)
  * detection sigma   <- link budget + noise model (Eqs. 1-2)
  * per-event energy  <- Table 3 constants
  * kernel PhotonicConfig + scheduler AcceleratorConfig <- factories

The demo prints the derived physics for the three DPU organizations,
then executes a zoo network end-to-end at the HEANA equal-area point and
shows the executed-trace energy/FPS/W agreeing with the analytic
perf-model prediction — and a deliberately incoherent kernel config
being rejected by the executor.

Run:  PYTHONPATH=src python examples/operating_point.py
"""
import jax

from repro.core import hw
from repro.core import perf_model as pm
from repro.core.types import Dataflow
from repro.exec import PlanCache, execute_cnn, plan_for_network
from repro.models.zoo_cnn import ZOO


def main():
    print("## Derived operating points (B=4)\n")
    print("| backend | DR GS/s | N | DPUs | P_pd dBm | sigma_rel | ENOB |")
    print("|---|---|---|---|---|---|---|")
    for be in ("heana", "amw", "maw"):
        for dr in (1.0, 5.0, 10.0):
            d = hw.OperatingPoint.equal_area(be, Dataflow.OS,
                                             dr).describe()
            print(f"| {be} | {dr:g} | {d['dpe_size']} | {d['n_dpus']} | "
                  f"{d['pd_power_dbm']:.2f} | {d['noise_sigma_rel']:.4f} "
                  f"| {d['enob']:.2f} |")

    model = ZOO["resnet_mini"]
    op = hw.OperatingPoint.equal_area("heana", Dataflow.OS, 1.0,
                                      noise_enabled=False)
    params = model.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, *model.in_hw, model.in_ch))
    plan = plan_for_network(params, op, batch=2, in_hw=model.in_hw,
                            lowering=model.graph, cache=PlanCache())
    res = execute_cnn(params, x, plan, op.kernel_config(), impl="pallas",
                      lowering=model.graph).block_until_ready()
    te = res.energy()
    ana = pm.cnn_inference(model.gemms(params), plan.acc, batch=2,
                           dataflows=list(plan.dataflows))
    print(f"\n## {model.name} executed at the HEANA equal-area point\n")
    print(f"   executed-trace: fps={te.fps:.1f}  fps/W="
          f"{te.fps_per_watt:.1f}  uJ/img={te.j_per_image * 1e6:.3f}")
    print(f"   analytic model: fps={ana.fps:.1f}  fps/W="
          f"{ana.fps_per_watt:.1f}")
    print(f"   coherent by construction: rel gap = "
          f"{abs(te.fps_per_watt - ana.fps_per_watt) / ana.fps_per_watt:.1e}")
    top = max(res.traces, key=lambda t: t.executed_energy_j)
    print(f"   hottest layer: {top.name} "
          f"({top.executed_energy_j * 1e6:.2f} uJ, "
          f"{top.adc_conversions} ADC conversions, {top.dataflow})")

    print("\n## Incoherent kernel configs are rejected\n")
    try:
        execute_cnn(params, x, plan, op.kernel_config(bits=6),
                    impl="ref", lowering=model.graph)
    except ValueError as e:
        print("   " + str(e).splitlines()[0])
        print("   (full message names every disagreeing field and the "
              "OperatingPoint fix)")


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
