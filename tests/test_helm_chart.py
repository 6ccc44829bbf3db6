"""Helm chart render + structural-invariant tests.

No helm binary ships in the CI/TPU images, so the chart is rendered with
the in-repo Go-template-subset renderer
(production_stack_tpu/testing/helm_render.py) and every manifest is
yaml-parsed — the clusterless equivalent of the reference's helm CI
(.github/workflows/functionality-helm-chart.yml:25-50, ct.yaml lint).

The TPU-first invariants checked here are the ones the round-2 verdict
called out: google.com/tpu resources + GKE TPU nodeSelectors instead of
nvidia.com/gpu (reference _helpers.tpl:94-117), no nvidia runtimeClass, no
/dev/shm for TP, and RBAC that actually matches the router's pod-watch
discovery.
"""

import json
import os

import pytest
import yaml

from production_stack_tpu.testing.helm_render import render_chart

CHART_DIR = os.path.join(os.path.dirname(__file__), "..", "helm")


def load_manifests(rendered):
    """yaml-parse every rendered template into a flat list of objects."""
    objs = []
    for name, text in rendered.items():
        for doc in yaml.safe_load_all(text):
            if doc:
                objs.append(doc)
    return objs


def by_kind(objs, kind):
    return [o for o in objs if o.get("kind") == kind]


def tpu_values():
    with open(os.path.join(CHART_DIR, "values-tpu-example.yaml")) as f:
        return yaml.safe_load(f)


def ci_values():
    with open(os.path.join(CHART_DIR, "values-ci.yaml")) as f:
        return yaml.safe_load(f)


def test_default_values_render_clean():
    objs = load_manifests(render_chart(CHART_DIR, release_name="test"))
    kinds = {o["kind"] for o in objs}
    # No modelSpec -> router plane + RBAC + PDB only.
    assert kinds == {
        "Deployment", "Service", "ServiceAccount", "Role", "RoleBinding",
        "PodDisruptionBudget",
    }
    router = by_kind(objs, "Deployment")[0]
    assert router["metadata"]["name"] == "test-deployment-router"


def test_tpu_example_renders_tpu_first():
    objs = load_manifests(
        render_chart(CHART_DIR, tpu_values(), release_name="prod")
    )
    deployments = {o["metadata"]["name"]: o for o in by_kind(objs, "Deployment")}
    engine = deployments["prod-llama3-8b-deployment-engine"]
    pod = engine["spec"]["template"]["spec"]
    container = pod["containers"][0]

    # TPU resources on requests AND limits; never nvidia.com/gpu.
    assert container["resources"]["requests"]["google.com/tpu"] == "8"
    assert container["resources"]["limits"]["google.com/tpu"] == "8"
    flat = json.dumps(objs)
    assert "nvidia.com/gpu" not in flat
    assert "runtimeClassName" not in flat  # no nvidia runtime class
    assert "/dev/shm" not in flat  # TP rides ICI, not shm (no NCCL)

    # GKE TPU node pool scheduling.
    assert pod["nodeSelector"] == {
        "cloud.google.com/gke-tpu-accelerator": "tpu-v5-lite-podslice",
        "cloud.google.com/gke-tpu-topology": "2x4",
    }
    assert {
        "key": "google.com/tpu", "operator": "Exists", "effect": "NoSchedule"
    } in pod["tolerations"]

    # Engine command drives the JAX engine with the mesh matching the chips.
    cmd = container["command"]
    assert "production_stack_tpu.engine.server.api_server" in cmd
    assert cmd[cmd.index("--data-parallel") + 1] == "2"
    assert cmd[cmd.index("--tensor-parallel") + 1] == "4"
    dp = int(cmd[cmd.index("--data-parallel") + 1])
    tp = int(cmd[cmd.index("--tensor-parallel") + 1])
    assert dp * tp == 8  # == requestTPU
    # KV offload tier + remote store wired through.
    assert cmd[cmd.index("--host-offload-gb") + 1] == "60"
    assert cmd[cmd.index("--remote-kv-url") + 1] == \
        "kv://prod-cache-server-service:9400"

    # hf_token as string -> generated secret reference.
    env = {e["name"]: e for e in container["env"]}
    ref = env["HF_TOKEN"]["valueFrom"]["secretKeyRef"]
    assert ref == {"name": "prod-secrets", "key": "hf_token_llama3-8b"}
    secrets = by_kind(objs, "Secret")
    assert secrets[0]["stringData"]["hf_token_llama3-8b"] == "hf_xxxxxxxxxxxxx"

    # PVC + HF_HOME on the volume.
    assert env["HF_HOME"]["value"] == "/data"
    pvcs = by_kind(objs, "PersistentVolumeClaim")
    assert pvcs[0]["metadata"]["name"] == "prod-llama3-8b-storage-claim"
    assert pvcs[0]["spec"]["resources"]["requests"]["storage"] == "60Gi"

    # Cache server deployment + service present.
    assert "prod-deployment-cache-server" in deployments
    cache_cmd = deployments["prod-deployment-cache-server"]["spec"]["template"][
        "spec"]["containers"][0]["command"]
    assert "production_stack_tpu.kvserver.server" in cache_cmd


def test_chat_template_configmap_and_mount():
    """modelSpec.chatTemplate -> per-model ConfigMap, read-only mount at
    /templates, and --chat-template on the engine command (reference
    deployment-vllm-multi.yaml:260-270)."""
    values = tpu_values()
    values["servingEngineSpec"]["modelSpec"][0]["chatTemplate"] = (
        "{% for m in messages %}{{ m.role }}: {{ m.content }}\n{% endfor %}"
    )
    objs = load_manifests(render_chart(CHART_DIR, values, release_name="ct"))
    cms = {o["metadata"]["name"]: o for o in by_kind(objs, "ConfigMap")}
    cm = cms["ct-llama3-8b-chat-template"]
    assert "{% for m in messages %}" in cm["data"]["chat-template.jinja"]

    engine = [
        o for o in by_kind(objs, "Deployment")
        if o["metadata"]["name"] == "ct-llama3-8b-deployment-engine"
    ][0]
    pod = engine["spec"]["template"]["spec"]
    container = pod["containers"][0]
    cmd = container["command"]
    assert cmd[cmd.index("--chat-template") + 1] == "/templates/chat-template.jinja"
    mounts = {m["name"]: m for m in container["volumeMounts"]}
    assert mounts["chat-template"]["mountPath"] == "/templates"
    assert mounts["chat-template"]["readOnly"] is True
    volumes = {v["name"]: v for v in pod["volumes"]}
    assert volumes["chat-template"]["configMap"]["name"] == \
        "ct-llama3-8b-chat-template"

    # decodeWindow flows through when set.
    values["servingEngineSpec"]["modelSpec"][0]["engineConfig"][
        "decodeWindow"] = 8
    objs = load_manifests(render_chart(CHART_DIR, values, release_name="ct"))
    engine = [
        o for o in by_kind(objs, "Deployment")
        if o["metadata"]["name"] == "ct-llama3-8b-deployment-engine"
    ][0]
    cmd = engine["spec"]["template"]["spec"]["containers"][0]["command"]
    assert cmd[cmd.index("--decode-window") + 1] == "8"


def test_router_rbac_matches_discovery():
    """The Role must grant exactly what k8s_discovery.py uses (pods
    get/list/watch) and the router args must select the fixed engine label
    the chart stamps on every engine pod."""
    objs = load_manifests(
        render_chart(CHART_DIR, tpu_values(), release_name="r")
    )
    role = by_kind(objs, "Role")[0]
    assert role["rules"] == [{
        "apiGroups": [""], "resources": ["pods"],
        "verbs": ["get", "watch", "list"],
    }]
    binding = by_kind(objs, "RoleBinding")[0]
    assert binding["subjects"][0]["name"] == "r-router-service-account"
    assert binding["roleRef"]["name"] == "r-pod-reader"

    router = [
        d for d in by_kind(objs, "Deployment")
        if d["metadata"]["name"] == "r-deployment-router"
    ][0]
    pod = router["spec"]["template"]["spec"]
    assert pod["serviceAccountName"] == "r-router-service-account"
    args = pod["containers"][0]["args"]
    selector = args[args.index("--k8s-label-selector") + 1]
    engine = [
        d for d in by_kind(objs, "Deployment")
        if d["metadata"]["name"] == "r-llama3-8b-deployment-engine"
    ][0]
    labels = engine["spec"]["template"]["metadata"]["labels"]
    for pair in selector.split(","):
        key, value = pair.split("=")
        assert labels.get(key) == value
    # The selector carries release identity: two releases in one namespace
    # must not discover each other's engines.
    assert "app.production-stack-tpu/release=r" in selector
    # k8s-port must match the engine container port.
    assert args[args.index("--k8s-port") + 1] == "8000"


def test_release_isolation_in_selectors():
    """Every workload selector includes the release label, so two releases
    sharing a namespace never adopt each other's pods."""
    objs = load_manifests(
        render_chart(CHART_DIR, tpu_values(), release_name="rel-a")
    )
    for deployment in by_kind(objs, "Deployment"):
        sel = deployment["spec"]["selector"]["matchLabels"]
        assert sel.get("app.production-stack-tpu/release") == "rel-a", (
            deployment["metadata"]["name"]
        )
        pod_labels = deployment["spec"]["template"]["metadata"]["labels"]
        assert pod_labels.get("app.production-stack-tpu/release") == "rel-a"
    for service in by_kind(objs, "Service"):
        assert service["spec"]["selector"].get(
            "app.production-stack-tpu/release"
        ) == "rel-a", service["metadata"]["name"]
    pdb = by_kind(objs, "PodDisruptionBudget")[0]
    assert pdb["spec"]["selector"]["matchLabels"][
        "app.production-stack-tpu/release"] == "rel-a"


def test_engine_probes_use_named_port():
    """Default probes target the named container port so overriding
    servingEngineSpec.containerPort can't orphan the probe."""
    objs = load_manifests(render_chart(CHART_DIR, tpu_values()))
    engine = [
        d for d in by_kind(objs, "Deployment")
        if "deployment-engine" in d["metadata"]["name"]
    ][0]
    container = engine["spec"]["template"]["spec"]["containers"][0]
    assert container["startupProbe"]["httpGet"]["port"] == "engine-cport"
    assert container["livenessProbe"]["httpGet"]["port"] == "engine-cport"


def test_ci_values_run_fake_engines():
    objs = load_manifests(
        render_chart(CHART_DIR, ci_values(), release_name="ci")
    )
    engine = [
        d for d in by_kind(objs, "Deployment")
        if d["metadata"]["name"] == "ci-fake-llama-deployment-engine"
    ][0]
    container = engine["spec"]["template"]["spec"]["containers"][0]
    assert "production_stack_tpu.testing.fake_engine" in container["command"]
    assert engine["spec"]["replicas"] == 2
    # No TPU ask in CI: no nodeSelector, no TPU resources.
    assert "nodeSelector" not in engine["spec"]["template"]["spec"]
    assert "google.com/tpu" not in json.dumps(container["resources"])
    # Session routing configured.
    router = [
        d for d in by_kind(objs, "Deployment")
        if d["metadata"]["name"] == "ci-deployment-router"
    ][0]
    args = router["spec"]["template"]["spec"]["containers"][0]["args"]
    assert args[args.index("--routing-logic") + 1] == "session"
    assert args[args.index("--session-key") + 1] == "x-user-id"


def test_static_discovery_variant():
    overrides = {
        "routerSpec": {
            "serviceDiscovery": "static",
            "staticBackends": "http://e1:8000,http://e2:8000",
            "staticModels": "m1,m2",
        }
    }
    objs = load_manifests(render_chart(CHART_DIR, overrides))
    router = by_kind(objs, "Deployment")[0]
    args = router["spec"]["template"]["spec"]["containers"][0]["args"]
    assert args[args.index("--static-backends") + 1] == "http://e1:8000,http://e2:8000"
    assert "--k8s-label-selector" not in args


def test_required_values_enforced():
    from production_stack_tpu.testing.helm_render import HelmTemplateError

    bad = {
        "servingEngineSpec": {
            "modelSpec": [{
                "name": "x", "repository": "img", "tag": "t",
                "requestTPU": 4,  # no tpuAccelerator/tpuTopology
                "engineConfig": {"modelPreset": "tiny-llama"},
            }]
        }
    }
    with pytest.raises(HelmTemplateError, match="tpuAccelerator"):
        render_chart(CHART_DIR, bad)


def test_values_match_schema():
    """Both shipped values files validate against values.schema.json
    (at minimum: types/enums/required fields are internally consistent)."""
    with open(os.path.join(CHART_DIR, "values.schema.json")) as f:
        schema = json.load(f)
    try:
        import jsonschema
    except ImportError:
        pytest.skip("jsonschema not installed")
    with open(os.path.join(CHART_DIR, "values.yaml")) as f:
        jsonschema.validate(yaml.safe_load(f), schema)
    jsonschema.validate(tpu_values(), schema)
    jsonschema.validate(ci_values(), schema)


def test_schema_rejects_the_removed_engine_value():
    """numSchedulerSteps (decodeWindow's old name) is gone from the chart:
    the template passes no flag for it, so the schema must refuse it
    rather than let it be set and silently ignored."""
    jsonschema = pytest.importorskip("jsonschema")
    with open(os.path.join(CHART_DIR, "values.schema.json")) as f:
        schema = json.load(f)
    values = tpu_values()
    engine_config = values["servingEngineSpec"]["modelSpec"][0]["engineConfig"]
    engine_config["decodeWindow"] = 4
    jsonschema.validate(values, schema)
    engine_config["numSchedulerSteps"] = 4
    with pytest.raises(jsonschema.ValidationError, match="numSchedulerSteps"):
        jsonschema.validate(values, schema)


def test_ingress_renders_when_enabled():
    overrides = {"routerSpec": {"ingress": {"enabled": True}}}
    objs = load_manifests(render_chart(CHART_DIR, overrides, release_name="i"))
    ingress = by_kind(objs, "Ingress")[0]
    rule = ingress["spec"]["rules"][0]
    assert rule["host"] == "tpu-router.local"
    backend = rule["http"]["paths"][0]["backend"]["service"]
    assert backend["name"] == "i-router-service"


def test_multihost_slice_renders_statefulset_pod_group():
    """tpuNumWorkers > 1 (v5e-16 = 4x4 = 4 workers x 4 chips) must render
    a StatefulSet pod group with a headless worker service and the
    jax.distributed bootstrap env — the TPU analogue of the reference's
    TP-over-/dev/shm plumbing (deployment-vllm-multi.yaml:198-228) and
    SURVEY §7's "multi-host slices need StatefulSet-like pod groups"."""
    with open(os.path.join(CHART_DIR, "values-multihost-example.yaml")) as f:
        values = yaml.safe_load(f)
    objs = load_manifests(
        render_chart(CHART_DIR, values, release_name="ms")
    )
    # Engine is a StatefulSet, not a Deployment (router stays Deployment).
    stss = by_kind(objs, "StatefulSet")
    assert len(stss) == 1
    sts = stss[0]
    assert sts["metadata"]["name"] == "ms-llama-3-8b-engine"
    assert sts["spec"]["replicas"] == 4
    assert sts["spec"]["podManagementPolicy"] == "Parallel"
    assert sts["spec"]["serviceName"] == "ms-llama-3-8b-engine-workers"
    deployments = [d["metadata"]["name"] for d in by_kind(objs, "Deployment")]
    assert deployments == ["ms-deployment-router"]

    pod = sts["spec"]["template"]["spec"]
    container = pod["containers"][0]
    env = {e["name"]: e for e in container["env"]}
    assert env["PSTPU_NUM_PROCESSES"]["value"] == "4"
    assert (env["PSTPU_PROCESS_ID"]["valueFrom"]["fieldRef"]["fieldPath"]
            == "metadata.labels['apps.kubernetes.io/pod-index']")
    assert (env["PSTPU_COORDINATOR_ADDRESS"]["value"]
            == "ms-llama-3-8b-engine-0.ms-llama-3-8b-engine-workers"
               ".default.svc:8476")
    # Per-worker chip count + multi-host topology selectors.
    assert container["resources"]["limits"]["google.com/tpu"] == "4"
    assert pod["nodeSelector"]["cloud.google.com/gke-tpu-topology"] == "4x4"

    # Two services: the client-facing one pinned to ordinal 0, and the
    # headless bootstrap service covering every worker.
    services = {s["metadata"]["name"]: s for s in by_kind(objs, "Service")}
    facing = services["ms-llama-3-8b-engine-service"]
    assert (facing["spec"]["selector"]["statefulset.kubernetes.io/pod-name"]
            == "ms-llama-3-8b-engine-0")
    headless = services["ms-llama-3-8b-engine-workers"]
    # k8s expects the literal string "None" for headless services.
    assert headless["spec"]["clusterIP"] == "None"
    assert headless["spec"]["publishNotReadyAddresses"] is True
    assert "statefulset.kubernetes.io/pod-name" not in headless["spec"]["selector"]


def test_multihost_slice_pdb_and_liveness_contract():
    """The slice-coherent lifecycle's chart half: slice pods carry the
    slice-group label, the generic release PDB EXCLUDES them (one
    voluntary eviction must never decapitate a live slice), a per-slice
    maxUnavailable: 0 PDB covers them, and --slice-member-timeout-s is
    threaded onto the StatefulSet command (stackcheck SC709 pins the
    same invariants statically)."""
    with open(os.path.join(CHART_DIR, "values-multihost-example.yaml")) as f:
        values = yaml.safe_load(f)
    objs = load_manifests(render_chart(CHART_DIR, values, release_name="ms"))

    sts = by_kind(objs, "StatefulSet")[0]
    assert sts["metadata"]["labels"][
        "app.production-stack-tpu/slice-group"] == "llama-3-8b"
    assert sts["spec"]["selector"]["matchLabels"][
        "app.production-stack-tpu/slice-group"] == "llama-3-8b"
    assert sts["spec"]["template"]["metadata"]["labels"][
        "app.production-stack-tpu/slice-group"] == "llama-3-8b"
    container = sts["spec"]["template"]["spec"]["containers"][0]
    cmd = container["command"]
    assert cmd[cmd.index("--slice-member-timeout-s") + 1] == "10"
    # preStop + termination grace cover every ordinal (the follower's
    # /drain relays to the leader — api_server._run_follower).
    assert "/drain" in json.dumps(container["lifecycle"]["preStop"])
    assert sts["spec"]["template"]["spec"][
        "terminationGracePeriodSeconds"] == 60

    pdbs = {p["metadata"]["name"]: p
            for p in by_kind(objs, "PodDisruptionBudget")}
    assert set(pdbs) == {"ms-pdb", "ms-llama-3-8b-slice-pdb"}
    generic = pdbs["ms-pdb"]
    assert generic["spec"]["selector"]["matchExpressions"] == [
        {"key": "app.production-stack-tpu/slice-group",
         "operator": "DoesNotExist"}
    ]
    slice_pdb = pdbs["ms-llama-3-8b-slice-pdb"]
    assert slice_pdb["spec"]["maxUnavailable"] == 0
    assert slice_pdb["spec"]["selector"]["matchLabels"][
        "app.production-stack-tpu/slice-group"] == "llama-3-8b"

    # Knob off: no slice PDB rendered (exclusion stays — slice pods are
    # never under the generic budget either way).
    values["servingEngineSpec"]["slicePodDisruptionBudget"] = False
    objs = load_manifests(render_chart(CHART_DIR, values, release_name="ms"))
    names = [p["metadata"]["name"] for p in by_kind(objs, "PodDisruptionBudget")]
    assert names == ["ms-pdb"]


def test_single_host_unchanged_by_multihost_support():
    """tpuNumWorkers absent or 1 keeps the plain-Deployment rendering."""
    values = tpu_values()
    objs = load_manifests(
        render_chart(CHART_DIR, values, release_name="sh")
    )
    assert by_kind(objs, "StatefulSet") == []
    names = [d["metadata"]["name"] for d in by_kind(objs, "Deployment")]
    assert any(n.endswith("-deployment-engine") for n in names)
    for d in by_kind(objs, "Deployment"):
        env = d["spec"]["template"]["spec"]["containers"][0].get("env", [])
        assert "PSTPU_NUM_PROCESSES" not in {e["name"] for e in env}
        # Single-host pods never carry the slice-group label (they must
        # stay under the generic PDB's DoesNotExist selector) nor the
        # slice liveness flag.
        labels = d["spec"]["template"]["metadata"]["labels"]
        assert "app.production-stack-tpu/slice-group" not in labels
        cmd = d["spec"]["template"]["spec"]["containers"][0].get(
            "command", [])
        assert "--slice-member-timeout-s" not in cmd


def test_router_dynamic_config_mount():
    """routerSpec.dynamicConfig.enabled wires the operator pipeline into
    the chart: ConfigMap projected at /dynamic, --dynamic-config-json
    flag, optional:true so the router boots before the first reconcile
    (consumed by .github/workflows/minikube-e2e.yml)."""
    values = ci_values()
    values.setdefault("routerSpec", {})["dynamicConfig"] = {"enabled": True}
    objs = load_manifests(render_chart(CHART_DIR, values, release_name="dc"))
    router = [d for d in by_kind(objs, "Deployment")
              if d["metadata"]["name"] == "dc-deployment-router"][0]
    pod = router["spec"]["template"]["spec"]
    container = pod["containers"][0]
    args = container["args"]
    idx = args.index("--dynamic-config-json")
    assert args[idx + 1] == "/dynamic/dynamic_config.json"
    mounts = {m["name"]: m for m in container["volumeMounts"]}
    assert mounts["dynamic-config"]["mountPath"] == "/dynamic"
    vols = {v["name"]: v for v in pod["volumes"]}
    cm = vols["dynamic-config"]["configMap"]
    assert cm["name"] == "dc-dynamic-config"
    assert cm["optional"] is True
    # Explicit name override flows through.
    values["routerSpec"]["dynamicConfig"]["configMapName"] = "custom-cm"
    objs = load_manifests(render_chart(CHART_DIR, values, release_name="dc"))
    router = [d for d in by_kind(objs, "Deployment")
              if d["metadata"]["name"] == "dc-deployment-router"][0]
    vols = {v["name"]: v
            for v in router["spec"]["template"]["spec"]["volumes"]}
    assert vols["dynamic-config"]["configMap"]["name"] == "custom-cm"
    # And off by default: no mount, no flag.
    objs = load_manifests(
        render_chart(CHART_DIR, ci_values(), release_name="dc")
    )
    router = [d for d in by_kind(objs, "Deployment")
              if d["metadata"]["name"] == "dc-deployment-router"][0]
    container = router["spec"]["template"]["spec"]["containers"][0]
    assert "--dynamic-config-json" not in container["args"]


# -- stackcheck SC7xx: the deployment-contract checker, end to end ----------
#
# A fixture chart pair drives tools/stackcheck's deployment rules the way
# SC3xx is driven by the metrics fixtures: the GOOD chart renders (via the
# in-repo helm_render, the clusterless `helm template` stand-in) and passes
# clean; the BAD chart ALSO renders — every seeded break deploys fine and
# only fails in production — and must flag all six rule kinds, including
# the deliberately mismatched values default (maxNumSeqs 16 vs argparse 8).

STACKCHECK_HELM = os.path.join(
    os.path.dirname(__file__), "fixtures", "stackcheck_helm"
)


def _sc7_config(root):
    from pathlib import Path

    from tools.stackcheck import Config
    from tools.stackcheck.config import DeploymentSurface, RoleContract

    return Config(
        repo_root=Path(root),
        package_dirs=("binpkg",),
        helm_values_path="helm/values.yaml",
        helm_schema_path="helm/values.schema.json",
        helm_overlay_paths=(),
        robustness_docs_path="docs/robustness.md",
        # SC708: fixture registry + autoscaling surfaces.
        registry_path="registry.py",
        observability_yaml_paths=(
            "observability/prom-adapter.yaml",
            "observability/hpa-example.yaml",
        ),
        hpa_template_paths=("helm/templates/hpa.yaml",),
        prom_adapter_path="observability/prom-adapter.yaml",
        deployment_surfaces=(
            DeploymentSurface(
                template="helm/templates/deployment-engine.yaml",
                argparse_file="binpkg/server.py",
                route_files=("binpkg/server.py",),
                values_spec="servingEngineSpec",
                drain_values_spec="servingEngineSpec",
            ),
            DeploymentSurface(
                template="helm/templates/deployment-router.yaml",
                argparse_file="binpkg/router.py",
                route_files=("binpkg/router.py",),
                values_spec="routerSpec",
            ),
        ),
        role_contract=RoleContract(
            engine_template="helm/templates/deployment-engine.yaml",
            engine_argparse_file="binpkg/server.py",
            router_template="helm/templates/deployment-router.yaml",
            router_argparse_file="binpkg/router.py",
        ),
    )


def test_stackcheck_good_chart_renders_and_passes_sc7():
    from tools.stackcheck import run_checks

    root = os.path.join(STACKCHECK_HELM, "good")
    rendered = render_chart(os.path.join(root, "helm"))
    assert load_manifests(rendered), "good fixture chart must render"
    assert run_checks(_sc7_config(root), families=["deployment"]) == []


def test_stackcheck_bad_chart_renders_but_flags_every_seeded_break():
    from tools.stackcheck import run_checks

    root = os.path.join(STACKCHECK_HELM, "bad")
    # The chart still template-renders: none of these breaks is a render
    # error — that is exactly why the static cross-check exists.
    assert load_manifests(render_chart(os.path.join(root, "helm")))

    violations = run_checks(_sc7_config(root), families=["deployment"])
    details = {(v.rule, v.detail) for v in violations}
    # SC701: flag not on the binary's argparse surface.
    assert ("SC701", "--log-level") in details
    # SC702: the ISSUE-required mismatched values default (16 vs 8).
    assert ("SC702", "servingEngineSpec.maxNumSeqs!=--max-num-seqs") in details
    # SC703: probe paths that are not registered routes (values + template).
    assert ("SC703", "/readyz") in details
    assert ("SC703", "/healthz") in details
    # SC703: /drain IS a route, but POST-only — kubelet probes GET.
    assert ("SC703", "/drain") in details
    # SC704: kubelet SIGKILL deadline inside the drain budget.
    assert any(
        r == "SC704" and "termination<=grace" in d for r, d in details
    )
    # SC705: template references a key the schema does not declare.
    assert ("SC705", "servingEngineSpec.typoKey") in details
    # SC706: docs table drifted from values.yaml (changed + removed key).
    assert ("SC706", "servingEngineSpec.maxNumSeqs:default") in details
    assert ("SC706", "servingEngineSpec.removedKey") in details
    # SC707 (ISSUE seed): the role label is rendered on the role-pool
    # Deployments but under a key the router's --k8s-role-label never
    # selects — the chart deploys, role discovery returns None for every
    # pod, and the fleet silently runs fused.
    assert ("SC707", "role_label:app.disagg-role!=app.role") in details
    # SC709 (ISSUE seeds): pod-group invariants that deploy fine and
    # deadlock at the first collective (or die at the first eviction).
    assert ("SC709", "mesh_product:slice") in details
    assert ("SC709", "slice_label_missing") in details
    assert ("SC709", "client_service_unpinned") in details
    assert ("SC709", "headless_not_ready_unpublished") in details
    assert ("SC709", "sts_prestop_missing") in details
    assert ("SC709", "sts_termination_missing") in details
    assert ("SC709", "generic_pdb_includes_slices") in details
    assert ("SC709", "slice_pdb_missing") in details
    # SC708: the adapter queries a family the registry doesn't know
    # (renamed series — matches nothing, HPA never scales) ...
    assert ("SC708", "tpu:num_requests_wating") in details
    # ... an HPA consumes a custom metric no adapter rule exposes ...
    assert ("SC708", "hpa:tpu_queue_depth") in details
    assert ("SC708", "hpa:tpu_router_headroom_slots") in details
    # ... and a helm HPA template annotation names an unregistered family.
    assert ("SC708", "tpu_router:fleet_headroom") in details


def test_stackcheck_sc704_equality_flags_and_yaml_allow_suppresses(tmp_path):
    """termination == grace must still flag — the termination countdown
    also covers the preStop hook and teardown, so equality SIGKILLs a
    drain that uses its full budget — and a values-side `# stackcheck:
    allow=SC704 reason=...` records a deliberate divergence and
    suppresses it."""
    import shutil

    from tools.stackcheck import run_checks

    root = tmp_path / "tree"
    shutil.copytree(os.path.join(STACKCHECK_HELM, "good"), root)
    values = root / "helm" / "values.yaml"
    equal = values.read_text().replace(
        "terminationGracePeriodSeconds: 60",
        "terminationGracePeriodSeconds: 30",
    )
    values.write_text(equal)
    violations = run_checks(_sc7_config(root), families=["deployment"])
    assert any(
        v.rule == "SC704" and v.detail.endswith("termination<=grace")
        for v in violations
    ), violations

    values.write_text(equal.replace(
        "terminationGracePeriodSeconds: 30",
        "terminationGracePeriodSeconds: 30"
        "  # stackcheck: allow=SC704 reason=no preStop hook on this pod",
    ))
    assert run_checks(_sc7_config(root), families=["deployment"]) == []


def test_stackcheck_sc707_invalid_role_value_flags(tmp_path):
    """A roles[].role value outside the engine binary's --disagg-role
    choices validates against the schema (it's just a string) and
    renders fine — the pool pod only crash-loops at deploy time.  SC707
    catches it statically."""
    import shutil

    from tools.stackcheck import run_checks

    root = tmp_path / "tree"
    shutil.copytree(os.path.join(STACKCHECK_HELM, "good"), root)
    values = root / "helm" / "values.yaml"
    values.write_text(values.read_text().replace(
        '- role: "prefill"', '- role: "prefil"'
    ))
    violations = run_checks(_sc7_config(root), families=["deployment"])
    assert any(
        v.rule == "SC707" and v.detail == "role_value:prefil"
        for v in violations
    ), violations


def test_stackcheck_sc709_mesh_mutation_flags(tmp_path):
    """Mutating the GOOD chart's slice mesh (tp 8 -> 4 under 2x4 chips)
    validates against any schema and renders fine — the slice only
    deadlocks at its first collective.  SC709 catches it statically, and
    a values-side allow records a deliberate divergence."""
    import shutil

    from tools.stackcheck import run_checks

    root = tmp_path / "tree"
    shutil.copytree(os.path.join(STACKCHECK_HELM, "good"), root)
    values = root / "helm" / "values.yaml"
    broken = values.read_text().replace(
        "tensorParallel: 8", "tensorParallel: 4"
    )
    values.write_text(broken)
    violations = run_checks(_sc7_config(root), families=["deployment"])
    assert any(
        v.rule == "SC709" and v.detail == "mesh_product:slice"
        for v in violations
    ), violations

    values.write_text(broken.replace(
        "modelSpec:",
        "# stackcheck: allow=SC709 reason=fixture divergence test\n"
        "  modelSpec:",
    ))
    assert run_checks(_sc7_config(root), families=["deployment"]) == []


def test_role_pools_render_per_role_deployments():
    """servingEngineSpec.roles renders one Deployment + role-labeled
    Service per role per model, each passing --disagg-role and carrying
    the role label the router's discovery selects (routerSpec
    k8sRoleLabel); role selectors stay disjoint so the prefill and
    decode Deployments of one model never adopt each other's pods."""
    values = tpu_values()
    values["servingEngineSpec"]["roles"] = [
        {"role": "prefill", "replicaCount": 1, "maxNumSeqs": 4},
        {"role": "decode", "replicaCount": 3},
    ]
    values.setdefault("routerSpec", {})["routingLogic"] = "disagg"
    objs = load_manifests(render_chart(CHART_DIR, values, release_name="dz"))
    deps = {o["metadata"]["name"]: o for o in by_kind(objs, "Deployment")}
    # The fused engine deployment is REPLACED by the role pools.
    assert "dz-llama3-8b-deployment-engine" not in deps
    pre = deps["dz-llama3-8b-prefill-deployment-engine"]
    dec = deps["dz-llama3-8b-decode-deployment-engine"]
    assert pre["spec"]["replicas"] == 1 and dec["spec"]["replicas"] == 3
    for d, role, mns in ((pre, "prefill", "4"), (dec, "decode", "32")):
        cmd = d["spec"]["template"]["spec"]["containers"][0]["command"]
        assert cmd[cmd.index("--disagg-role") + 1] == role
        # Per-role maxNumSeqs override; decode falls back to engineConfig.
        assert cmd[cmd.index("--max-num-seqs") + 1] == mns
        # The handoff rides the shared store.
        assert cmd[cmd.index("--remote-kv-url") + 1] == \
            "kv://dz-cache-server-service:9400"
        labels = d["spec"]["template"]["metadata"]["labels"]
        assert labels["app.production-stack-tpu/role"] == role
        assert d["spec"]["selector"]["matchLabels"][
            "app.production-stack-tpu/role"] == role
    svcs = {s["metadata"]["name"]: s for s in by_kind(objs, "Service")}
    assert svcs["dz-llama3-8b-prefill-engine-service"]["spec"]["selector"][
        "app.production-stack-tpu/role"] == "prefill"
    # The router passes the matching role-label flag (SC707's contract).
    router_args = deps["dz-deployment-router"]["spec"]["template"]["spec"][
        "containers"][0]["args"]
    assert router_args[router_args.index("--k8s-role-label") + 1] == \
        "app.production-stack-tpu/role"


def test_hpa_renders_router_and_per_role_pools():
    """templates/hpa.yaml: routerSpec.autoscaling renders a router HPA;
    roles[].maxReplicas renders one HPA per role pool targeting the
    matching Deployment, with the role-appropriate adapter metric names
    (prefill = queued prompt tokens, decode = queue depth + deadline-miss
    rate) — the names stackcheck SC708 cross-checks against
    observability/prom-adapter.yaml and the metric registry."""
    overrides = {
        "routerSpec": {"autoscaling": {
            "enabled": True, "minReplicas": 1, "maxReplicas": 4,
            "targetInflightPerPod": 200,
        }},
        "servingEngineSpec": {
            "modelSpec": [{
                "name": "llama", "repository": "r", "tag": "t",
                "engineConfig": {"modelPreset": "tiny-llama"},
            }],
            "roles": [
                {"role": "prefill", "replicaCount": 1, "maxReplicas": 4},
                {"role": "decode", "replicaCount": 2, "minReplicas": 2,
                 "maxReplicas": 12, "targetQueueDepth": 2},
            ],
        },
    }
    objs = load_manifests(render_chart(CHART_DIR, overrides, release_name="as"))
    hpas = {o["metadata"]["name"]: o for o in by_kind(
        objs, "HorizontalPodAutoscaler")}
    assert set(hpas) == {
        "as-router-hpa", "as-llama-prefill-engine-hpa",
        "as-llama-decode-engine-hpa",
    }

    def metric_names(hpa):
        return [m["pods"]["metric"]["name"] for m in hpa["spec"]["metrics"]]

    router = hpas["as-router-hpa"]
    assert router["spec"]["scaleTargetRef"]["name"] == "as-deployment-router"
    assert metric_names(router) == ["tpu_router_inflight_requests"]

    pre = hpas["as-llama-prefill-engine-hpa"]
    assert pre["spec"]["scaleTargetRef"]["name"] == \
        "as-llama-prefill-deployment-engine"
    assert pre["spec"]["minReplicas"] == 1 and pre["spec"]["maxReplicas"] == 4
    assert metric_names(pre) == ["tpu_queued_prompt_tokens"]

    dec = hpas["as-llama-decode-engine-hpa"]
    assert dec["spec"]["scaleTargetRef"]["name"] == \
        "as-llama-decode-deployment-engine"
    assert dec["spec"]["minReplicas"] == 2 and dec["spec"]["maxReplicas"] == 12
    assert metric_names(dec) == [
        "tpu_num_requests_waiting", "tpu_deadline_miss_rate"]
    depth = dec["spec"]["metrics"][0]["pods"]["target"]["averageValue"]
    assert str(depth) == "2"

    # Autoscaling off + no role min/max: no HPA objects at all.
    objs = load_manifests(render_chart(CHART_DIR, release_name="off"))
    assert by_kind(objs, "HorizontalPodAutoscaler") == []
